"""`chip_smoke.py`'s control flow at tiny width on the CPU.

The script itself demands a TPU; its work lives in `chip_smoke.run`,
which takes the config and the platform it expects, so these tests run
the same phases, checks and verdict line on the host with interpret-mode
kernels.  The four-chip paths run in a fresh interpreter that forces
four host devices before JAX starts.
"""
import dataclasses
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.core.rcllm import tiny_lm_config

ROOT = Path(__file__).resolve().parents[1]
SMALL = dict(n_items=60, n_requests=4, decode_steps=3)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _records(out: str):
    return [json.loads(line) for line in out.strip().splitlines()]


def test_one_chip_path_on_cpu(smoke, capsys):
    cfg = dataclasses.replace(tiny_lm_config(n_layers=2), dtype="bfloat16")
    smoke.run(cfg, "cpu", arena_bytes=8 << 20, **SMALL)
    recs = _records(capsys.readouterr().out)
    verdict = recs[-1]
    assert set(verdict) == {"ok", "device"} and verdict["ok"] is True
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert verdict["device"]["platform"] == "cpu"
    serve = {r["backend"]: r for r in recs if r.get("phase") == "serve"}
    assert set(serve) == {"jnp", "pallas"}
    assert all(r["requests"] == SMALL["n_requests"] for r in serve.values())
    refs = [r for r in recs if r.get("phase") == "reference"]
    assert [r["backend"] for r in refs] == ["jnp", "pallas"]
    assert all(r["rel_l2"] <= smoke.REF_REL_L2 for r in refs)
    (parity,) = [r for r in recs if r.get("phase") == "backend_parity"]
    assert parity["first_token_rel_l2_max"] <= smoke.BACKEND_REL_L2


@pytest.mark.parametrize("what", ["platform", "tolerance"])
def test_failed_check_prints_no_verdict(smoke, capsys, monkeypatch, what):
    """A failed check raises out of `run`, so no verdict line is printed
    (the script then exits non-zero)."""
    cfg = dataclasses.replace(tiny_lm_config(n_layers=2), dtype="bfloat16")
    if what == "platform":
        with pytest.raises(AssertionError, match="this run needs 'tpu'"):
            smoke.run(cfg, "tpu", arena_bytes=8 << 20, **SMALL)
    else:
        monkeypatch.setattr(smoke, "REF_REL_L2", 0.0)
        with pytest.raises(AssertionError, match="float32 reference"):
            smoke.run(cfg, "cpu", arena_bytes=8 << 20, **SMALL)
    assert '"ok"' not in capsys.readouterr().out


def test_main_refuses_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    assert capsys.readouterr().out == ""


def test_four_chip_paths_on_four_host_devices():
    """``--chips 4``'s paths (k=4 replicas on four devices, tp=4) on four
    forced host devices, in an interpreter that has not touched JAX."""
    code = (
        "import sys\n"
        f"sys.path.insert(0, {str(ROOT)!r})\n"
        "import chip_smoke\n"
        "from repro.core.rcllm import tiny_lm_config\n"
        "cfg = tiny_lm_config(n_layers=2, n_heads=8, n_kv_heads=4)\n"
        "chip_smoke.run(cfg, 'cpu', four=True, arena_bytes=8 << 20,"
        f" **{SMALL!r})\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-4000:]
    recs = _records(res.stdout)
    assert recs[-1]["ok"] is True and recs[-1]["device"]["count"] == 4
    (k4,) = [r for r in recs if r.get("phase") == "cluster_k4"]
    assert len(set(k4["worker_devices"])) == 4
    (tp4,) = [r for r in recs if r.get("phase") == "tp4"]
    assert tp4["tokens_equal_tp1"] and tp4["first_token_rel_l2_max"] <= tp4["tol"]
