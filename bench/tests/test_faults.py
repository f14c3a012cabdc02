"""A run with the timed path broken underneath comes out as not correct.

Each case drives the rest of a run (set-up, the closed loop, the sampled
comparison with the reference) on the CPU, without the harness's look for
a chip, at each cell's widths with a small batch and window and one pool
entry, so that the answers compared do not depend on the window's timing.
The
program is wrapped so that it answers half of each batch with the other
half's logits, or alters one answer of each request where it is
produced. The sound program, unbroken, comes out correct.
"""
import time

import jax.numpy as jnp
import pytest

from bench import harness


def half_batch(make):
    def program(cfg):
        fn = make(cfg)

        def apply(params, x):
            h = x.shape[0] // 2
            out = fn(params, x[:x.shape[0] - h])
            return jnp.concatenate([out, out[:h]])
        return apply
    return program


def answer_altered(make):
    def program(cfg):
        fn = make(cfg)

        def apply(params, x):
            out = fn(params, x)
            return out.at[0].set(out[0, ::-1])
        return apply
    return program


CASES = [("sf4-256.cifar.b128", None), ("sf4-256.cifar.b128", half_batch),
         ("sf4-256.cifar.b128", answer_altered),
         ("vgg11.cifar.b128", None), ("vgg11.cifar.b128", half_batch),
         ("vgg11.cifar.b128", answer_altered),
         ("sf4-256.cifar.b1", None), ("sf4-256.cifar.b1", answer_altered),
         ("vgg11.cifar.b1", None), ("vgg11.cifar.b1", answer_altered)]


@pytest.mark.parametrize("name, fault", CASES, ids=[
    f"{n}-{f.__name__ if f else 'sound'}" for n, f in CASES])
def test_broken_program_is_not_correct(monkeypatch, name, fault):
    cell = harness.load_cell(name)
    cell.mix.update(batch=min(cell.mix["batch"], 4), pool_requests=1,
                    check_requests=3)
    monkeypatch.setattr(harness, "load_cell", lambda _: cell)
    program = fault(cell.model.program) if fault else None
    result = harness.run(name, 2**32 + 3, 1.0, False, time.perf_counter(),
                         program=program)
    assert result["attempted"] >= 3
    assert result["correct"] is (fault is None), result["checks"]
    assert list(result)[-1] == "checks"
