"""A fault planted under the interleaved hybrid's timed path, beside
``bench.faults``'s, to show that the check sees the Mamba-2 mechanism:

* ``ssd_state_not_carried``: the SSD scan's state does not cross chunk
  boundaries; every chunk of ``chunk_size`` steps starts from a zero
  state, as if each were a sequence of its own.

The same fault planted in the reference is
``bench.drivers.hybrid_train.control_readings``' reading of it.
"""
from __future__ import annotations

import contextlib

HYBRID = ("ssd_state_not_carried",)


@contextlib.contextmanager
def hybrid(fault: str):
    """Break ``repro.models.ssm``'s chunked SSD in this process only."""
    if fault not in HYBRID:
        raise ValueError(f"unknown fault {fault!r}; one of {HYBRID}")
    from repro.models import ssm as S
    real = S.ssd_chunked

    def each_chunk_alone(x, dt, a, bm, cm, chunk, init_state=None):
        b, length = x.shape[:2]
        q = min(chunk, length)
        if length % q:
            raise ValueError(f"{length} steps are no whole chunks of {q}")
        fold = lambda t: t.reshape((b * (length // q), q) + t.shape[2:])
        y, state = real(fold(x), fold(dt), a, fold(bm), fold(cm), chunk)
        last = state.reshape((b, length // q) + state.shape[1:])[:, -1]
        return y.reshape(x.shape), last

    S.ssd_chunked = each_chunk_alone
    try:
        yield
    finally:
        S.ssd_chunked = real
