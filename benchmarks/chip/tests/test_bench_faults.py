"""A whole run on the CPU at a small size, with the timed path broken
underneath: ``correct`` must come out false for each fault a serving
cell can have, and true with none."""

import pytest

from fqabench import harness
from fqabench.faults import altered_tokens, stale_cache


def run_cell(root, hook=None, trace=0, seed=2**31 + 77):
    return harness.run(
        ["--workload", "small.small", "--seed", str(seed),
         "--seconds", "2", "--trace", str(trace)],
        root=root, bench_dir=root / "benchmarks" / "chip",
        require_tpu=False, engine_hook=hook)


def test_sound_run_is_correct(small_root):
    out = run_cell(small_root)
    assert out["correct"], out["compared"]
    assert out["compared"]["compiles_in_window"]["value"] == 0
    assert set(out["metrics"]) == {"out_tokens_per_s", "itl_p99_ms",
                                   "setup_s"}
    assert list(out)[-1] == "compared"


@pytest.mark.parametrize("fault", [altered_tokens, stale_cache],
                         ids=["alter_tokens", "stale_state"])
def test_broken_path_is_not_correct(small_root, fault):
    out = run_cell(small_root, hook=fault)
    assert not out["correct"], out["compared"]
    assert out["failed"] > 0
