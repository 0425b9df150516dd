"""The int8 control through the harness's own comparison, at a size a
test run can hold: on each seed a window of the small cell reads
correct, and the int8 reference put in the program's place, on the same
prompts and served tokens, reads not correct against the cell's own
limits."""

import pytest

from fqabench import harness


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_int8_control_is_not_correct(small_root, seed):
    cell = harness.Cell("small.small", seed, root=small_root,
                        bench_dir=small_root / "benchmarks" / "chip",
                        require_tpu=False)
    w, _, compiles = cell.measure(seed, 2.0)
    seqs = cell.sample(w, seed)
    assert compiles == 0 and seqs
    sound = cell.check(seed, seqs)
    assert harness.judge(sound), sound
    control = cell.check(seed, seqs, control=True)
    assert not harness.judge(control), control
