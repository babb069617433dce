"""chip_smoke.py on the CPU: the device gate refuses, and every phase's
checks pass (and can fail) at a tiny size.

The script's phases take their sizes as arguments; ``main`` runs them at
full size only behind the TPU gate, so these tests call the phases
directly on the CPU device.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


TINY_WHATIF = {
    "pSortMB": [16.0, 256.0, 1024.0],
    "pSortFactor": [5.0, 100.0],
    "pNumReducers": [2.0, 128.0],
    "pSortRecPerc": [0.01, 0.15],
}
TINY_PLANNER = {
    "pNumNodes": [8.0, 1024.0],
    "pMaxMapsPerNode": [2.0],
    "pMaxRedPerNode": [2.0],
    "pReduceSlowstart": [0.05],
    "arrivalRate": [0.05, 0.2],
    "schedPolicy": [0.0, 1.0],
}


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_gate_refuses_without_a_tpu(smoke, capsys, argv):
    assert smoke.main(argv) == 1
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "no TPU" in out.err


def test_whatif_grid_phase(smoke):
    top = smoke.phase_whatif_grid(jax.devices(), space=TINY_WHATIF,
                                  n_sample=16)
    assert top.n_evaluated == 24 and len(top.entries) == 10


def test_oracle_check_fails_on_a_wrong_cost(smoke):
    ev = smoke.ChunkedEvaluator(*smoke.WHATIF_BASE, chunk=8)
    rows = smoke.sample_space(TINY_WHATIF, 4, seed=1)
    out = ev.evaluate(rows).outputs
    args = (rows, out["j_totalCost"], out["valid"] > 0, out)
    smoke._against_oracle("test", smoke.WHATIF_BASE, *args)
    with pytest.raises(smoke.SmokeFailure, match="equal round counts"):
        smoke._against_oracle("test", smoke.WHATIF_BASE, rows,
                              out["j_totalCost"] * 1.01, *args[2:])


def test_whatif_service_phase(smoke):
    smoke.phase_whatif_service(jax.devices(), n_queries=8)


def test_planner_phase(smoke):
    smoke.phase_planner(jax.devices(), space=TINY_PLANNER, n_jobs=16,
                        n_agree=2)


def test_sharded_phase(smoke):
    smoke.phase_sharded(jax.devices(), space=TINY_WHATIF,
                        planner_space=TINY_PLANNER, n_jobs=16)


def test_last_line_is_the_contract_json(smoke, monkeypatch, capsys):
    """With the gate passed and no phase failing, the last line is exactly
    the JSON object the contract names."""

    class Tpu:
        platform, device_kind = "tpu", "TPU v5 lite"

    monkeypatch.setattr(smoke.jax, "devices", lambda: [Tpu()])
    monkeypatch.setattr(smoke, "use_compile_cache", lambda root: "<cache>")
    for name in ("phase_whatif_grid", "phase_whatif_service", "phase_planner"):
        monkeypatch.setattr(smoke, name, lambda devs: None)
    assert smoke.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}

    def broken(devs):
        raise smoke.SmokeFailure("wrong")

    monkeypatch.setattr(smoke, "phase_planner", broken)
    assert smoke.main([]) == 1
    assert '"ok"' not in capsys.readouterr().out
