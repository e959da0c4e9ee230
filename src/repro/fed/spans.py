"""The federated round's stage names, as a profiler trace shows them.

Device work inside the jitted round sits under :func:`scope`
(``jax.named_scope``): the name becomes one segment of the ``op_name``
metadata of every op traced under it, so the device trace can put each op
down to its stage. Host work around the round sits under :func:`span`
(``jax.profiler.TraceAnnotation``): an event on the profiler's host plane,
on the device trace's clock, recorded only while a profiler session is
active. Neither changes what the round computes, and with no profiler
running a span costs one small object per call.
"""
import jax

#: the model's own stages (inside fl.client_sgd) and its counters
from repro.models.common import (COUNTERS, MLA, MODEL_SCOPES,  # noqa: F401
                                 MOE_EXPERTS, MOE_ROUTE, MOE_ROUTED_ROWS)

#: device stages (named scopes inside the jitted round)
CLIENT_SGD = "fl.client_sgd"   # the local-SGD scan
DECISION = "fl.decision"       # the Algorithm-1 store step
CODEC = "fl.codec"             # the client-side wire encode
FOLD = "fl.fold"               # aggregation, bank write-back, server step
SCOPES = (CLIENT_SGD, DECISION, CODEC, FOLD)

#: host stages (profiler spans around the round)
PREFETCH_WAIT = "fl.prefetch_wait"  # the round waiting on a staged batch
HOST_PREP = "fl.host_prep"          # index draws, gather, batch layout
H2D = "fl.h2d"                      # staging the batch on the device
READBACK = "fl.readback"            # the round's metrics read to the host
SPANS = (PREFETCH_WAIT, HOST_PREP, H2D, READBACK)


def scope(name: str):
    """Device stage ``name`` for the ops traced inside the block."""
    return jax.named_scope(name)


def span(name: str):
    """Host stage ``name`` for the block's wall time."""
    return jax.profiler.TraceAnnotation(name)
