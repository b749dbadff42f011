"""Faults planted under the timed path, to show that the check catches
them. Each is a context manager that breaks the program in this process
only, and undoes it on exit:

* ``state_unchanged``: a step returns the state it was given;
* ``half_batch``: half of each batch (block) is left out, the mean taken
  over the rest;
* ``no_exchange``: the all-reduce between workers is left out;
* ``answer_altered``: an answer is altered where it is produced (the
  SVM job's weights; the LM step's new weights, one leaf moved double);
* ``token_altered``: a token is altered where it is produced (the LM
  trainer's data pipeline).
"""
from __future__ import annotations

import contextlib

import jax

SVM = ("state_unchanged", "half_batch", "no_exchange", "answer_altered")
LM = ("state_unchanged", "half_batch", "answer_altered", "token_altered")


@contextlib.contextmanager
def _patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


@contextlib.contextmanager
def svm(fault: str):
    """Break ``repro.core.svm``'s shard_map program."""
    from repro.core import svm as S
    make_block, make_program = S._make_worker_block, S.dms_shard_map_program

    def broken_block(*args, **kw):
        block = make_block(*args, **kw)
        if fault == "state_unchanged":
            return lambda carry, x, y, a: carry
        if fault == "half_batch":
            return lambda carry, x, y, a: block(carry, x[:x.shape[0] // 2],
                                                y[:y.shape[0] // 2], a)
        return block

    def broken_program(*args, **kw):
        fn = make_program.__wrapped__(*args, **kw)
        if fault != "answer_altered":
            return fn

        def altered(w, xs, ys):
            out = fn(w, xs, ys)
            return out.at[0].add(jax.numpy.mean(jax.numpy.abs(out)))
        return altered

    with contextlib.ExitStack() as stack:
        stack.enter_context(_patched(S, "_make_worker_block", broken_block))
        stack.enter_context(_patched(S, "dms_shard_map_program",
                                     broken_program))
        if fault == "no_exchange":
            stack.enter_context(_patched(jax.lax, "pmean",
                                         lambda x, axis_name: x))
        yield


@contextlib.contextmanager
def lm(fault: str):
    """Break the step ``repro.launch.train.build_trainer`` jits."""
    from repro.core import local_sgd as LS
    make = LS.make_train_step

    def broken(*args, **kw):
        step = make(*args, **kw)

        def run(state, batch):
            if fault == "half_batch":
                batch = {k: v[:v.shape[0] // 2] for k, v in batch.items()}
            new, metrics = step(state, batch)
            if fault == "state_unchanged":
                new = state
            if fault == "answer_altered":       # one weight moved double
                p, p0 = new["params"], state["params"]
                emb = 2 * p["embed"]["embedding"] - p0["embed"]["embedding"]
                new = {**new, "params": {**p, "embed": {"embedding": emb}}}
            return new, metrics
        return run

    from repro.data import pipeline as P
    host_batch = P.DataPipeline._host_batch

    def altered(self, step):
        b = host_batch(self, step)
        tokens = b["tokens"].copy()
        tokens[0, 0] = (tokens[0, 0] + 1) % self.model_cfg.vocab_size
        return {**b, "tokens": tokens}

    with contextlib.ExitStack() as stack:
        stack.enter_context(_patched(LS, "make_train_step", broken))
        if fault == "token_altered":
            stack.enter_context(_patched(P.DataPipeline, "_host_batch",
                                         altered))
        yield
