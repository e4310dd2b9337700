"""The comparison that decides ``correct`` fails what it must: the
control (the program with one stated guarantee switched off) and each
fault the cells can have, planted under the timed path at a tiny size
on the CPU.  One-chip cells exchange nothing between chips, and a live
cell admits its traffic from the loop's queue, with no pre-scripted
batch to halve, so those faults have no place here."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import bench_tiny  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.make_root(str(tmp_path_factory.mktemp("bench")))


def hooks_for(root, **kw):
    h = bench_tiny.harness_of(root).Hooks()
    for key, value in kw.items():
        setattr(h, key, value)
    return h


def _wrap_runners(st, change):
    """Route every segment dispatch of stepper ``st`` through
    ``change(state_in, outputs) -> outputs``."""
    import jax.numpy as jnp

    def wrap(run):
        def wrapped(state, *a):
            kept = tuple(jnp.copy(x) for x in state)
            return change(kept, run(state, *a))
        return wrapped

    st.runner = wrap(st.runner)


def state_unchanged(st):
    _wrap_runners(st, lambda kept, out: (kept,) + tuple(out[1:]))


def answer_altered(st):
    def change(kept, out):
        state, stats, red = out
        return state, stats.at[0, 0].add(1), red
    _wrap_runners(st, change)


CASES = {
    "control: plain flooding, no link gate": dict(program_mode="r"),
    "fault: the step returns its state unchanged":
        dict(on_stepper=state_unchanged),
    "fault: an answer altered where it is produced":
        dict(on_stepper=answer_altered),
}


@pytest.mark.parametrize("cell, case", [(cell, case)
                                        for cell, _, _ in bench_tiny.CELLS
                                        for case in CASES])
def test_broken_timed_path_is_not_correct(root, cell, case):
    h = hooks_for(root, **CASES[case])
    rc, res, err = bench_tiny.run(root, cell, seed=9, seconds=1.0, hooks=h)
    assert rc == 0, err
    assert res["correct"] is False, res["checks"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())
