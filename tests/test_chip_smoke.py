"""chip_smoke.py's phases at tiny sizes on the CPU's virtual devices.

The chip runs the same code at the reference's sizes; here each phase
must pass end to end (verdicts against the oracle or the planted
anomaly, device engines only, no engine fallback) so a change that
breaks the smoke fails tier-1 before it costs a chip call.  The Pallas
kernels run in interpret mode (conftest).
"""

import json
import subprocess
import sys

import pytest

import chip_smoke

SMALL = dict(e2e_s=4, keys=40, key_ops=300, key_conc=5, oracle_keys=10,
             single_ops=3_000, crash_ops=3_000, deep_hists=3, deep_ops=300,
             deep_open=8, hc_ops=60, elle_txns=300, lattice_txns=200,
             small_txns=120, elle_threshold=256, lattice_threshold=128)


@pytest.fixture(scope="module")
def devices():
    import jax
    return jax.devices()


@pytest.mark.parametrize("phase", [
    pytest.param("e2e", marks=pytest.mark.slow),   # a real kvd daemon
    *chip_smoke.Smoke.PHASES[1:]])
def test_phase(phase, devices, capsys):
    smoke = chip_smoke.Smoke(SMALL, 7, devices[:1])
    ok = smoke.phase(phase, getattr(smoke, phase))
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert ok, line
    assert line["phase"] == phase and line["fallbacks"] == {}


def test_mesh_phase_spans_every_device(devices, capsys):
    smoke = chip_smoke.Smoke(SMALL, 7, devices[:4])
    assert smoke.run()
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["ok"] and line["devices"] == 4
    for name, path in line["paths"].items():
        assert path["result_devices"] == 4, name


def test_spans_refuses_a_result_on_one_device(devices):
    smoke = chip_smoke.Smoke(SMALL, 7, devices[:4])
    with pytest.raises(chip_smoke.PhaseFailed):
        smoke.spans([4, 4, 1], 4)


def test_no_accelerator_no_ok_line():
    """On a machine without a TPU the smoke exits non-zero and prints
    no result line."""
    p = subprocess.run([sys.executable, chip_smoke.__file__],
                       capture_output=True, text=True, timeout=120,
                       env={"JAX_PLATFORMS": "cpu",
                            "PATH": "/usr/bin:/bin"})
    assert p.returncode != 0
    assert '"ok"' not in p.stdout


def _ops(h):
    return [(o.process, o.type, o.f, o.value) for o in h.ops]


@pytest.mark.parametrize("kw", [{}, {"crash_rate": 0.01, "max_open": 6}])
def test_make_history_is_deterministic_per_seed(kw):
    a = chip_smoke.make_history(400, 5, seed=3, **kw)
    b = chip_smoke.make_history(400, 5, seed=3, **kw)
    c = chip_smoke.make_history(400, 5, seed=4, **kw)
    assert _ops(a) == _ops(b)
    assert _ops(a) != _ops(c)
    assert a.packed_columns() is not None


def test_planted_stale_read_is_refuted_at_its_read():
    from jepsen_tpu import models
    from jepsen_tpu.ops import wgl_cpu
    h = chip_smoke.make_history(600, 5, seed=11, vmax=9)
    assert wgl_cpu.check(models.CASRegister(), h)["valid?"] is True
    pos, w = chip_smoke.plant_stale_read(h, 0.9, 9)
    assert h.ops[pos].f == "read" and h.ops[pos].value == w
    r = wgl_cpu.check(models.CASRegister(), h)
    assert r["valid?"] is False
    assert r["op_index"] == chip_smoke.Smoke.invoke_of(h, pos)
