"""``correct`` comes out false when the timed path is broken, and the
correctness limit separates the program from its control.

The faults are planted under the harness, in the executor's entry that
the measured window drives, and the rest of a run goes as on the chip:
window, sample drawn from the seed, reference, comparison.  A CNN forward
has no state and no exchange between chips, so the faults it can have
are an answer altered where it is produced and half of the batch left
out.
"""
import time

import jax.numpy as jnp
import pytest

from bench import control, harness

from conftest import CPU


def _alter_one_answer(apply):
    def broken(self, batch, params=None):
        y = apply(self, batch, params)
        return y.at[0].multiply(1.001)
    return broken


def _drop_half_the_batch(apply):
    def broken(self, batch, params=None):
        half = apply(self, batch[: len(batch) // 2], params)
        return jnp.concatenate([half, half])
    return broken


@pytest.mark.parametrize("fault", [_alter_one_answer, _drop_half_the_batch],
                         ids=["answer_altered", "half_batch_left_out"])
def test_broken_timed_path_is_not_correct(tiny_root, monkeypatch, fault):
    from repro.runtime.executor import GraphExecutor
    monkeypatch.setattr(GraphExecutor, "apply", fault(GraphExecutor.apply))
    r = harness.run(harness.Registry(tiny_root), "tiny_resnet.b4", 17, 0.2,
                    False, time.perf_counter(), dict(CPU))
    assert r["correct"] is False
    assert r["failed"] >= 1
    chk = r["checks"]["max_rel_err"]
    assert chk["value"] > chk["limit"]


@pytest.mark.parametrize("cell", ["tiny_resnet.b4", "tiny_mobilenet.b4"])
def test_limit_lies_between_program_and_control(tiny_root, cell):
    """The program's reading is under the limit; the reference computed in
    three bfloat16 passes, put in the program's place, is over it."""
    r = control.readings(harness.Registry(tiny_root), cell, 23, 0.1)
    assert r["program"] < r["limit"] < r["control"]
