"""Each reference module's ``flops_per_token`` against the hand counts it
brings (``FLOPS_CASES``), and the totals of the benchmark's
configuration files."""
import json
from pathlib import Path

import pytest

from bench.reference import arch_module

BENCH = Path(__file__).resolve().parents[1]
CASES = [(ref, i) for ref in sorted(p.stem for p in
                                    (BENCH / "archs").glob("*.py"))
         for i in range(len(arch_module(ref).FLOPS_CASES))]


@pytest.mark.parametrize("ref,i", CASES, ids=[f"{r}-{i}" for r, i in CASES])
def test_flops_hand_count(ref, i):
    mod = arch_module(ref)
    model, seq_len, want = mod.FLOPS_CASES[i]
    got = mod.flops_per_token(model, seq_len)
    assert got == want
    assert got["total"] == sum(v for k, v in got.items() if k != "total")


@pytest.mark.parametrize("name,total", [("mamba2-370m", 2_518_155_264),
                                        ("mamba2-370m-l2", 401_866_752)])
def test_benchmark_configs(name, total):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    mod = arch_module(cfg["reference"])
    assert mod.flops_per_token(cfg["model"], 4096)["total"] == total
