"""Unified federated execution engine (paper Algorithms 1 & 3).

``FLEngine`` is the single round-runner behind every FL reproduction in
this repo. One jit'd round function is assembled from pieces resolved by
string key through the registries in ``repro.fed.registry`` (the
extension seam the declarative ``ExperimentSpec`` API builds on).

1. **Client scheduler** (``SCHEDULERS``) — how the K clients' local
   training is mapped onto the device. Every scheduler runs one step on a
   chunk of clients, :func:`chunk_step`: ``client_fn`` vmapped over the
   chunk, the payloads folded into the aggregation carry (or handed out,
   in collect mode), and the new bank rows gated by the participation
   mask.

   * ``"vmap"`` — the whole cohort is one chunk: O(K·M) transient memory.
   * ``"chunked"`` — a ``lax.scan`` of the step over blocks of at most
     ``chunk_size`` clients (:func:`pick_chunk`), the banks updated in
     place in the scan carry: O(chunk·M) transient memory, while the
     persistent bank still scales with K. The scan, the padding to the
     chunk grid and the per-client epilogue live in
     :class:`ChunkedScheduler` only.
   * ``"sharded"`` — the chunked scan with the step under ``shard_map`` on
     the named 2-D ``(clients, model)`` mesh (``FLConfig.mesh``). It
     overrides only how a chunk's bank rows are indexed, the shard_map
     around the step, and the ``model_sharding="auto"`` chunk function.
   * ``"buffered"`` — FedBuff-style asynchronous aggregation: the chunked
     scan computes every dispatched client, and each payload parks in a
     per-client staleness buffer until it folds in its arrival round
     (``repro.fed.latency``).

   Every fold is the aggregator's strictly sequential per-client
   accumulate in client order, so the schedulers share one float addition
   order: "sharded" on a [1, 1] mesh and "buffered" at zero latency are
   "chunked" bit for bit, and a multi-device sharded round only
   reassociates the final psum. A scheduler is a factory ``(cfg,
   num_clients) -> obj`` with ``chunk``/``pad`` ints plus
   ``prepare_batch(host_arrays)`` and ``run(client_fn, agg, params, batch,
   lbg, resid, w, maskf)``; an optional ``layout_banks(bank)`` hook lets
   it own the state banks' physical layout.

2. **Aggregator** (:func:`make_aggregator`) — the server rule
   (``FLConfig.aggregator``) and the payload it folds. ``"mean"`` streams:
   :class:`SparseTopKAggregator` folds a top-k store's ``(idx, val)``
   payload scaled by ``w·gscale`` (``gscale`` = rho on a recycle round, 1
   on a full round), :class:`SparseCodecAggregator` the quantized payload
   through the ``lbgm_dequant_fold`` kernel, and :class:`DenseAggregator`
   dense g_tilde (the ``fused_kernels=False`` oracle). A robust rule
   (``repro.fed.robust``) puts the step in collect mode: the per-client
   payloads are stacked and reduced once per round, O(K·M) peak.
   Hierarchical tiers (``repro.fed.hierarchy``) wrap the streaming
   aggregator.

3. **LBGStore** (``LBG_STORES``) — the look-back bank and Algorithm 1's
   accept/recycle decision: dense, top-k, top-k laid out over the mesh,
   top-k host-resident (``"topk-host"``: streamed a chunk at a time by
   :class:`_HostBankStreamer` through the same :func:`chunk_step`), or
   null (vanilla FL). A store implements ``init(params, K)``,
   ``client_step(grad, lbg_k)`` and ``full_round_cost(base_cost, stats)``
   and registers with ``@register_lbg_store("name")``.

4. **Uplink pipeline and wire codec** — ``make_uplink_pipeline`` (top-K /
   ATOMO / SignSGD plus error feedback, ``COMPRESSORS``) runs before the
   decision; the codec (``FLConfig.codec``, ``repro.comm.wire``) encodes
   the payload the uplink ships and counts its bytes.

``client_fn`` is ``post(pre(...))`` (:meth:`FLEngine._client_halves`):
``pre`` strips the engine-reserved batch keys and runs local SGD, the
Byzantine attack (``repro.fed.attacks``) and the pipeline; ``post`` runs
the decision, the uplink count and the codec. ``model_sharding="auto"``
swaps in only the store's block-row decision step. Each round ends in
:func:`server_step` and :func:`round_metrics`, shared by the synchronous,
buffered and topk-host rounds.

Uplink accounting follows the paper's metric of floating-point parameters
shared per worker: a scalar (recycle) round uploads exactly 1 float, a
full round pays the pipeline/store cost; the wire codec adds the real
bytes, and both land in the :class:`~repro.comm.accounting.CommLedger`.

On the host, :class:`RoundPrefetcher` prepares round t+1 while the device
runs round t, drawing the same rng stream as the synchronous path. Faults,
latencies and codec seeds draw from streams of their own, so a clean run
is unchanged by them, and checkpoints (``FLConfig.ckpt_every``) resume
bit for bit.

Each stage of the round carries a name from ``repro.fed.spans`` for a
profiler trace: the scopes ``fl.client_sgd``, ``fl.decision`` and
``fl.codec`` in ``client_fn``, and ``fl.fold`` around every fold, bank
write and the server step; the host spans ``fl.prefetch_wait``,
``fl.host_prep``, ``fl.h2d`` and ``fl.readback`` mark the host's share.
"""
from __future__ import annotations

import queue
import threading
import warnings
import weakref
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import ckpt as ckpt_lib
from repro.comm.accounting import CommLedger
from repro.comm.wire import WIRE_KEY, codec_rng, make_codec
from repro.compression import make_uplink_pipeline
from repro.core import lbgm as lbgm_lib
from repro.core.lbgm_sharded import (bank_model_partition,
                                     make_local_topk_step,
                                     make_mesh_topk_step)
from repro.core.tree_math import tree_size, tree_zeros_like
from repro.fed.attacks import (BYZ_KEY, STALE_KEY, fault_rng, make_attack,
                               select_byzantine)
from repro.fed.flconfig import FLConfig  # noqa: F401  (re-export)
from repro.fed.hierarchy import HierarchicalAggregator, make_tier_map
from repro.fed.latency import make_latency
from repro.fed.registry import (LBG_STORES, SCHEDULERS, register_lbg_store,
                                register_scheduler)
from repro.fed.robust import (CollectDenseAggregator,
                              CollectSparseAggregator,
                              ScalarMedianSparseAggregator, make_robust_rule)
from repro.fed.spans import (CLIENT_SGD, CODEC, COUNTERS, DECISION, FOLD,
                             H2D, HOST_PREP, PREFETCH_WAIT, READBACK, scope,
                             span)
from repro.kernels.lbgm_sparse import (fold_carry_shape,
                                       lbgm_dequant_fold_pallas)

#: reserved batch key: per-client local-step budgets (the buffered
#: scheduler's compute heterogeneity) — stripped before the SGD scan
TAU_KEY = "_tau"


def resolve_fused_kernels(cfg: FLConfig) -> bool:
    """Pallas half of the ``FLConfig.fused_kernels`` knob.

    ``None`` = auto: compiled Mosaic kernels on TPU only — everywhere else
    the Pallas interpreter would be slower than the XLA fallback, so auto
    turns them off. ``True`` forces them on (interpret mode off-TPU, used
    by the fused-path tests); ``False`` is the legacy 3-pass XLA path.
    """
    if cfg.fused_kernels is None:
        return jax.default_backend() == "tpu"
    return bool(cfg.fused_kernels)


# ------------------------------------------------------------- LBG stores

def _null_stats():
    return lbgm_lib.LBGMStats(
        sin2=jnp.ones((), jnp.float32), rho=jnp.zeros((), jnp.float32),
        sent_scalar=jnp.zeros((), bool),
        uplink_floats=jnp.zeros((), jnp.float32),
        grad_sq_norm=jnp.zeros((), jnp.float32))


class NullLBGStore:
    """Vanilla FL: no LBG bank, every round is a full round."""

    def init(self, params, num_clients: int):
        return {}

    def client_step(self, grad, lbg_k):
        return grad, lbg_k, _null_stats()

    def full_round_cost(self, base_cost, stats):
        return base_cost


class DenseLBGStore:
    """Paper-faithful Algorithm 1: one dense params-shaped LBG per client.

    ``fused=True`` routes the decision's three O(M) reductions through the
    one-pass Pallas projection kernel (``kernels.ops.lbgm_projection``,
    batched over the schedulers' client vmap axis).
    """

    def __init__(self, delta_threshold: float, fused: bool = False):
        self.delta = delta_threshold
        self.fused = fused

    def init(self, params, num_clients: int):
        return jax.tree.map(
            lambda p: jnp.zeros((num_clients,) + p.shape, p.dtype), params)

    def client_step(self, grad, lbg_k):
        return lbgm_lib.lbgm_client_step(grad, lbg_k, self.delta,
                                         fused=self.fused)

    def full_round_cost(self, base_cost, stats):
        # full rounds ship whatever the uplink pipeline produced
        return base_cost


class TopKLBGStore:
    """Sparse (idx, val) LBG bank at k_frac density (paper App. C.1).

    ``fused=True`` fuses the decision's three dense passes per leaf
    (gather, ||g||^2, block top-k) into one Pallas pass
    (``kernels.ops.lbgm_sparse_decision``). ``sparse_client_step`` /
    ``make_aggregator`` implement the sparse scalar-round aggregation
    contract (see the module docstring): the step emits only the (idx,
    val) payload + a gscale scalar, and the matching
    :class:`SparseTopKAggregator` scatter-adds it into the round
    aggregate — no per-client dense g_tilde anywhere.
    """

    def __init__(self, delta_threshold: float, k_frac: float = 0.1,
                 fused: bool = False):
        self.delta = delta_threshold
        self.k_frac = k_frac
        self.fused = fused

    def init(self, params, num_clients: int):
        proto = lbgm_lib.init_topk_lbg(params, self.k_frac)
        return jax.tree.map(
            lambda x: jnp.zeros((num_clients,) + x.shape, x.dtype), proto)

    def client_step(self, grad, lbg_k):
        return lbgm_lib.lbgm_topk_client_step(grad, lbg_k, self.delta,
                                              self.k_frac, fused=self.fused)

    def sparse_client_step(self, grad, lbg_k):
        """((send, gscale), new_lbg, stats) — no dense scatter."""
        return lbgm_lib.lbgm_topk_client_step(grad, lbg_k, self.delta,
                                              self.k_frac, sparse_out=True,
                                              fused=self.fused)

    def make_aggregator(self, params):
        return SparseTopKAggregator(params, self.k_frac)

    def full_round_cost(self, base_cost, stats):
        # the sparse-transmission cost model (values + block-local indices)
        # lives in core/lbgm.py; reuse its number rather than re-deriving
        return stats.uplink_floats


class ShardedTopKLBGStore(TopKLBGStore):
    """Sparse (idx, val) bank laid out for mesh sharding.

    Same bank shapes and cost model as :class:`TopKLBGStore`, but the
    accept/recycle decision goes through
    ``repro.core.lbgm_sharded.make_mesh_topk_step`` — the decision body of
    the 2-D ``(clients, model)`` mesh:

    * along the *client* axis the bank rows live on the device that trains
      their client (placed by ``ShardedScheduler.layout_banks``), so the
      per-client decision adds zero cross-device traffic;
    * with ``n_model > 1`` each leaf's block rows additionally shard over
      the *model* axis (where ``nb`` divides — see
      ``bank_model_partition``): every model rank gathers/top-ks only its
      own rows of the gradient's global block layout and the three
      decision scalars are psum-reduced over ``model`` before the
      accept/recycle branch. Per-device bank bytes drop to
      O(K·k_frac·M / (n_clients·n_model)).

    On ``n_model == 1`` this is exactly ``make_local_topk_step`` (no psum
    at all), numerically identical to ``TopKLBGStore`` — the two stores
    are interchangeable bit-for-bit on any scheduler.
    """

    def __init__(self, delta_threshold: float, k_frac: float = 0.1,
                 fused: bool = False, n_model: int = 1,
                 model_axis: str = "model"):
        super().__init__(delta_threshold, k_frac, fused=fused)
        self.n_model = int(n_model)
        self.model_axis = model_axis
        # dense g_tilde path (fused_kernels=False, dense aggregation):
        # always the full-leaf device-local step — with n_model > 1 the
        # banks stay model-replicated and every rank decides identically
        self._step = make_local_topk_step(delta_threshold, k_frac,
                                          fused=fused)
        self._sparse_step = make_mesh_topk_step(
            delta_threshold, k_frac, n_model=self.n_model,
            model_axis=model_axis, sparse_out=True, fused=fused)

    def client_step(self, grad, lbg_k):
        return self._step(grad, lbg_k)

    def sparse_client_step(self, grad, lbg_k):
        return self._sparse_step(grad, lbg_k)

    def blocked_sparse_step(self, layouts):
        """Decision step for ``model_sharding="auto"``: gradients arrive
        already in block-row layout, pre-sliced to the calling model
        rank's rows (the scheduler's nested shard_map boundary does the
        TP-layout -> block-row reshard once). ``layouts`` is the global
        ``name -> (nb, block, kb)`` tree; the decision math, psums, and
        uplink accounting are exactly ``sparse_client_step``'s."""
        return make_mesh_topk_step(
            self.delta, self.k_frac, n_model=self.n_model,
            model_axis=self.model_axis, sparse_out=True, fused=self.fused,
            pre_blocked=True, layouts=layouts)

    def bank_model_partition(self, params) -> Dict[str, bool]:
        """name -> whether that leaf's bank block rows shard over the
        model axis (the scheduler's placement and this store's decision
        slicing share the one rule in ``core.lbgm_sharded``)."""
        return bank_model_partition(params, self.k_frac, self.n_model)


class HostTopKLBGStore(TopKLBGStore):
    """Sparse (idx, val) bank kept host-resident (``"topk-host"``).

    Same decision math, cost model, and aggregator as
    :class:`TopKLBGStore`, but ``init`` allocates the (Kp, nb, kb) bank as
    NumPy on the host instead of a device array. The engine detects
    ``host_resident`` and switches ``run_round`` into the out-of-core
    chunk loop: each chunk runs :func:`chunk_step` under one jit
    (``FLEngine._chunk_fn``) while a :class:`_HostBankStreamer` daemon
    thread uploads chunk ``c+1``'s bank and batch rows and writes chunk
    ``c``'s updated rows back to the host array — so per-round *device*
    bank bytes are O(chunk * k_frac * M) regardless of K, where the
    chunked scheduler keeps the whole O(K * k_frac * M) bank on device.
    The step being the chunked scheduler's, histories are bit-for-bit
    equal to ``"topk"`` on the same seed (tier-1 tested).

    Requires ``scheduler="chunked"`` with streaming aggregation and no
    error-feedback residual (validated at FLConfig construction / engine
    build).
    """

    #: engine marker: run_round streams bank chunks from host memory
    host_resident = True

    def init(self, params, num_clients: int):
        proto = lbgm_lib.init_topk_lbg(params, self.k_frac)
        return jax.tree.map(
            lambda x: np.zeros((num_clients,) + tuple(x.shape),
                               np.dtype(x.dtype)), proto)


def _lbg_kw(cfg: FLConfig) -> dict:
    """User lbg_kw with an actionable error for engine-reserved keys
    (a raw collision would surface as a cryptic TypeError from the store
    constructor, against this repo's validated-config convention)."""
    kw = dict(cfg.lbg_kw or {})
    if "fused" in kw:
        raise ValueError(
            "FLConfig.lbg_kw: 'fused' is engine-controlled — set "
            "FLConfig.fused_kernels instead of passing it to the store")
    for reserved in ("n_model", "model_axis"):
        if reserved in kw:
            raise ValueError(
                f"FLConfig.lbg_kw: {reserved!r} is engine-controlled — "
                "the model axis comes from FLConfig.mesh ([clients, "
                "model]), not from store kwargs")
    return kw


register_lbg_store("null", lambda cfg: NullLBGStore())
register_lbg_store("dense", aliases=("full",))(
    lambda cfg: DenseLBGStore(cfg.delta_threshold,
                              fused=resolve_fused_kernels(cfg)))
register_lbg_store("topk")(
    lambda cfg: TopKLBGStore(cfg.delta_threshold,
                             fused=resolve_fused_kernels(cfg),
                             **_lbg_kw(cfg)))
register_lbg_store("topk-sharded")(
    lambda cfg: ShardedTopKLBGStore(cfg.delta_threshold,
                                    fused=resolve_fused_kernels(cfg),
                                    n_model=cfg.mesh_model_dim,
                                    **_lbg_kw(cfg)))
register_lbg_store("topk-host")(
    lambda cfg: HostTopKLBGStore(cfg.delta_threshold,
                                 fused=resolve_fused_kernels(cfg),
                                 **_lbg_kw(cfg)))


def make_lbg_store(cfg: FLConfig):
    """Resolve the configured LBG storage scheme through ``LBG_STORES``."""
    key = "null" if not cfg.use_lbgm else cfg.resolved_lbg_variant
    return LBG_STORES.get(key)(cfg)


# ------------------------------------------------------------ aggregators

class DenseAggregator:
    """Legacy accumulation: dense fp32 params-shaped carry, strictly
    sequential weighted sum over each client's dense g_tilde (O(M) flops
    and HBM traffic per client, whatever the round transmitted)."""

    def init(self, params):
        return tree_zeros_like(params, jnp.float32)

    def accumulate(self, acc, w, gt_stack):
        return _seq_weighted_sum(acc, w, gt_stack)

    def finalize(self, acc):
        return acc


class SparseTopKAggregator:
    """Sparse scalar-round aggregation for the top-k LBG stores.

    The carry is a per-leaf ``(nb, block)`` fp32 accumulator in the same
    block layout as the sparse bank. Each client k contributes exactly its
    transmitted payload: ``(w_k * gscale_k) * send.val`` scatter-added at
    ``send.idx`` — O(k_frac·M) per client instead of the dense path's
    O(M) scatter + O(M) add. Accumulation stays a strictly sequential
    per-client ``lax.scan`` (deterministic order; top-k indices are unique
    within a block row, so the scatter-add itself is order-free), and
    ``finalize`` reshapes back to the params layout once per round.

    Equivalence to :class:`DenseAggregator` (the oracle, kept behind
    ``fused_kernels=False``): bit-for-bit on full rounds (``gscale == 1``
    makes every addend ``w_k * val`` — same values, same order; untouched
    positions only ever add exact zeros), fp32-reassociation-tolerance on
    scalar rounds (``w_k * rho_k`` is folded before the multiply with the
    LBG values instead of after).
    """

    payload_keys = ("idx", "val")

    def __init__(self, params, k_frac: float):
        self._layout = {
            name: (leaf.shape, int(leaf.size))
            + lbgm_lib._block_layout(int(leaf.size), k_frac)[:2]
            for name, leaf in params.items()}

    def init(self, params):
        return {name: jnp.zeros((nb, block), jnp.float32)
                for name, (_, _, nb, block) in self._layout.items()}

    def accumulate(self, acc, w, out):
        send, gscale = out            # leaves (C, nb, kb); gscale (C,)

        def body(a, x):
            w_k, send_k, s_k = x
            coeff = w_k * s_k

            def upd(ai, sk):
                # gather-modify-scatter rather than scatter-add: the
                # update is then the same `a + where(w>0, c*v, 0)`
                # expression the dense path accumulates with, so XLA's
                # FMA contraction applies identically and full rounds stay
                # bit-for-bit equal to DenseAggregator (a scatter-add
                # rounds the multiply separately — off by 1 ulp). Sound
                # because top-k indices are unique within a block row.
                # The w_k > 0 gate mirrors _seq_weighted_sum: phantom pad
                # clients may carry NaN values/gscale.
                rows = jnp.arange(ai.shape[0])[:, None]
                cur = ai[rows, sk["idx"]]
                new = cur + jnp.where(w_k > 0, coeff * sk["val"], 0.0)
                return ai.at[rows, sk["idx"]].set(new)

            return {name: upd(a[name], send_k[name]) for name in a}, None

        acc, _ = jax.lax.scan(body, acc, (w, send, gscale))
        return acc

    def finalize(self, acc):
        return {name: acc[name].reshape(-1)[:size].reshape(shape)
                for name, (shape, size, _, _) in self._layout.items()}


class SparseCodecAggregator(SparseTopKAggregator):
    """Streaming aggregation of QUANTIZED sparse payloads.

    The same strictly sequential per-client fold as
    :class:`SparseTopKAggregator`, but each client's payload arrives in
    the wire layout ``{idx, val (int8/fp8), scale}`` and the dequantize
    (widen + per-block-row scale multiply) happens inside the fold: one
    ``kernels.lbgm_sparse.lbgm_dequant_fold_pallas`` call per leaf per
    chunk (interpreted off the TPU), so the fp32 (C, nb, kb) payload stack
    is never materialized. The carry holds each block row as the kernel's
    (n_tiles, T) tile matrix (``fold_carry_shape``), read and written once
    per chunk, in place. Each client's row expands to that dense matrix
    by a one-hot MXU matmul, O(C·nb·block) MACs; the O(C·nb·kb) XLA
    gather-scatter it replaces (``kernels.ref.lbgm_dequant_accum_ref``,
    now the tests' oracle) ran at a TPU scatter's serial rate, about
    22 ns an entry on a v5e.
    """

    payload_keys = ("idx", "val", "scale")

    def init(self, params):
        return {name: jnp.zeros(fold_carry_shape(nb, block), jnp.float32)
                for name, (_, _, nb, block) in self._layout.items()}

    def accumulate(self, acc, w, out):
        send, gscale = out   # idx/val (C, nb, kb); scale (C, nb, 1)
        return {name: lbgm_dequant_fold_pallas(acc[name], w, gscale,
                                               sk["idx"], sk["val"],
                                               sk["scale"])
                for name, sk in send.items()}

    def finalize(self, acc):
        return super().finalize(
            {name: acc[name].reshape(nb, -1)[:, :block]
             for name, (_, _, nb, block) in self._layout.items()})


def make_aggregator(cfg: FLConfig, store, params, codec):
    """Resolve the round aggregation strategy for ``(cfg, store)``.

    Two orthogonal choices meet here. The *payload* (sparse vs dense):
    sparse scalar-round payloads whenever the store supports them and
    ``fused_kernels`` is not explicitly ``False`` (pure XLA, so unlike the
    Pallas kernels it pays off on every backend). The *rule*
    (``cfg.aggregator``, resolved through the AGGREGATORS registry):
    ``"mean"`` keeps the streaming fold above — the exact legacy code
    path, bit-for-bit with pre-robustness histories — while every robust
    rule (trimmed_mean / coordinate_median / geometric_median / ...)
    switches the schedulers into *collect* mode: a median cannot be
    folded one client at a time, so the per-client payload stacks (dense
    g_tilde or sparse (idx, val) + gscale) are collected across chunks
    and reduced once per round (see ``repro.fed.robust``).

    The *codec* is the third axis: a lossy codec hands sparse payloads to
    the dequantizing fold (:class:`SparseCodecAggregator`) on the
    streaming path, and hands the collect adapters its ``decode_leaf`` /
    ``payload_keys`` so the robust rules see fp32 values again. The
    ``scalar_median`` rule additionally demands the sparse payload
    structure itself — it never densifies, so it has no dense fallback.
    """
    rule = make_robust_rule(cfg)
    sparse = (cfg.fused_kernels is not False
              and hasattr(store, "make_aggregator"))
    if getattr(rule, "scalar_structured", False) and not sparse:
        raise ValueError(
            f"aggregator={cfg.aggregator!r} exploits the sparse "
            "scalar-round payload structure and has no dense fallback — "
            "use a top-k LBG store (lbg_variant='topk'/'topk-sharded') "
            "and leave fused_kernels unset or True")
    decode = codec.decode_leaf if codec.lossy else None
    pk = codec.payload_keys
    if getattr(rule, "streaming", False):
        if sparse:
            if codec.lossy:
                return SparseCodecAggregator(params, store.k_frac), True
            return store.make_aggregator(params), True
        return DenseAggregator(), False
    if getattr(rule, "scalar_structured", False):
        return ScalarMedianSparseAggregator(
            rule, params, store.k_frac, decode=decode,
            payload_keys=pk), True
    if sparse:
        return CollectSparseAggregator(rule, params, store.k_frac,
                                       decode=decode,
                                       payload_keys=pk), True
    return CollectDenseAggregator(rule), False


# ------------------------------------------------------------- schedulers

def pick_chunk(num_clients: int, chunk_size: int) -> int:
    """Actual scan-block size for the chunked scheduler.

    Prefer the largest divisor of K that fits in chunk_size — same memory
    bound, zero phantom-client compute. Only when K is so indivisible that
    the best divisor is under half the requested size (e.g. prime K) do we
    keep chunk_size and pay for a zero-weight padded tail block instead.
    """
    c = min(chunk_size, num_clients)
    d = max(x for x in range(1, c + 1) if num_clients % x == 0)
    return d if d >= max(1, c // 2) else c


def _seq_weighted_sum(acc, w, gt_stack):
    """acc + sum_k w[k] * gt_stack[k], accumulated strictly sequentially.

    Shared by both schedulers so the addition order (and therefore the
    float rounding) is identical regardless of how clients were batched.
    """
    def body(a, x):
        w_k, gt_k = x
        # the w_k > 0 gate (not just w_k *) keeps zero-weight clients out
        # even when their gradient is non-finite — phantom pad clients run
        # the user's loss_fn on all-zero batches, which may produce NaNs
        # that 0 * NaN would otherwise leak into the aggregate
        return jax.tree.map(
            lambda ai, gi: ai + jnp.where(
                w_k > 0, w_k * gi.astype(jnp.float32), 0.0), a, gt_k), None
    out, _ = jax.lax.scan(body, acc, (w, gt_stack))
    return out


def _counters(aux) -> Dict[str, jax.Array]:
    """The program counters (``spans.COUNTERS``) a loss reports in its
    metrics dict, as float32 scalars."""
    return {k: jnp.asarray(aux[k], jnp.float32) for k in COUNTERS
            if k in aux}


def _summed_counters(stats, maskf) -> Dict[str, jax.Array]:
    """Each counter of the per-client ``stats``, summed over the clients
    that ``maskf`` counts."""
    return {k: jnp.sum(v * maskf) for k, v in stats.items() if k != "loss"}


def _keep_sampled(maskf, new, old):
    """Unsampled clients keep their previous per-client state."""
    return jax.tree.map(
        lambda n, o: jnp.where(
            maskf.reshape((-1,) + (1,) * (n.ndim - 1)) > 0, n, o), new, old)


def _pad_to_grid(pad, *vecs):
    """Per-client (K,) vectors zero-padded to the chunk grid (K + pad,):
    phantom pad clients carry zero weight and a zero mask."""
    if not pad:
        return vecs
    return tuple(jnp.concatenate([v, jnp.zeros(pad, v.dtype)])
                 for v in vecs)


def _flat_clients(x):
    """(n_chunks, chunk, ...) scan output -> (Kp, ...)."""
    return x.reshape((-1,) + x.shape[2:])


def chunk_step(client_fn, params, fold, acc, b_c, l_c, r_c, w_c, m_c):
    """The one step every chunked path runs on a chunk of clients.

    ``client_fn`` is vmapped over the chunk's batch, bank rows and
    residual rows. ``fold(acc, w_c, payload)`` folds the payloads into the
    carry; with ``fold=None`` the payloads are handed out in the carry's
    place (collect mode, the buffered scheduler). The new bank and
    residual rows are gated by the chunk's mask, so an unsampled client
    keeps its state. Returns ``(acc or payload, new_lbg, new_res, loss,
    uplink, scalar, wire)``.
    """
    gt, nl, nr, loss, uplink, scalar, wire = jax.vmap(
        lambda b, l, r: client_fn(params, b, l, r))(b_c, l_c, r_c)
    out = gt
    if fold is not None:
        with scope(FOLD):
            out = fold(acc, w_c, gt)
    with scope(FOLD):
        nl = _keep_sampled(m_c, nl, l_c)
        nr = _keep_sampled(m_c, nr, r_c)
    return out, nl, nr, loss, uplink, scalar, wire


def round_weights(weights, mask):
    """``(maskf, w)``: the participation mask as float32, and the
    clients' data-size weights normalized over it."""
    maskf = mask.astype(jnp.float32)
    w = weights * maskf
    return maskf, w / jnp.maximum(jnp.sum(w), 1e-12)


def server_step(lr, params, agg):
    """The server update ``p - lr * agg``."""
    with scope(FOLD):
        return jax.tree.map(lambda p, a: p - lr * a.astype(p.dtype),
                            params, agg)


def round_metrics(losses, w, uplink, scalar, wire, maskf, countf=None):
    """The round's metrics dict. The loss is weighted by ``w``; uplink,
    wire bytes and the scalar-round share count the clients ``maskf``
    counts, and the program counters those of ``countf`` (default
    ``maskf``)."""
    countf = maskf if countf is None else countf
    return {
        "loss": jnp.sum(losses["loss"] * w),
        "uplink_floats": jnp.sum(uplink * maskf),
        "frac_scalar": jnp.sum(scalar.astype(jnp.float32) * maskf)
        / jnp.maximum(jnp.sum(maskf), 1.0),
        "wire_bytes": jnp.sum(wire * maskf),
        **_summed_counters(losses, countf),
    }


@register_scheduler("vmap")
class VmapScheduler:
    """All K clients in one vmap; O(K·M) transient working set."""

    def __init__(self, cfg: FLConfig, num_clients: int):
        self.chunk, self.pad = num_clients, 0

    def prepare_batch(self, stacked: Dict[str, np.ndarray]):
        return stacked  # leaves stay (K, tau, b, ...)

    def run(self, client_fn, agg, params, batch, lbg, resid, w, maskf):
        # the cohort is one chunk, and its fold is the whole aggregation
        if getattr(agg, "collect", False):
            # robust rules need the whole per-client stack at once —
            # vmap already has it in hand
            fold = lambda _, w, gt: agg.reduce(w, gt)
        else:
            fold = lambda _, w, gt: agg.finalize(
                agg.accumulate(agg.init(params), w, gt))
        return chunk_step(client_fn, params, fold, None, batch, lbg, resid,
                          w, maskf)


@register_scheduler("chunked")
class ChunkedScheduler:
    """lax.scan over blocks of `chunk` clients; O(chunk·M) transient set.

    The LBG / residual banks ride in the scan *carry* and are updated
    in place per chunk via dynamic_update_slice (rather than stacked as
    scan outputs), so XLA never materializes a second O(K·M) bank buffer.
    The engine allocates banks padded to the chunk grid (K + pad rows).
    """

    def __init__(self, cfg: FLConfig, num_clients: int):
        self.num_clients = num_clients
        self.chunk = pick_chunk(num_clients, cfg.chunk_size)
        self.pad = (-num_clients) % self.chunk

    def prepare_batch(self, stacked: Dict[str, np.ndarray]):
        """(K, tau, b, ...) -> (n_chunks, chunk, tau, b, ...), padded
        host-side so the device scan consumes the argument buffer
        directly (no device-side copy). The pad rows are written into one
        preallocated buffer (no extra concatenate copy of the K rows)."""
        chunk, pad = self.chunk, self.pad

        def to_chunks(x):
            if pad:
                padded = np.zeros((x.shape[0] + pad,) + x.shape[1:],
                                  x.dtype)
                padded[:x.shape[0]] = x
                x = padded
            return x.reshape((x.shape[0] // chunk, chunk) + x.shape[1:])
        return {k: to_chunks(v) for k, v in stacked.items()}

    # how a chunk's rows are read from and written to a (Kp, ...) bank
    def _read(self, bank, i):
        return jax.tree.map(lambda x: jax.lax.dynamic_slice_in_dim(
            x, i * self.chunk, self.chunk), bank)

    def _write(self, bank, rows, i):
        return jax.tree.map(lambda x, v: jax.lax.dynamic_update_slice_in_dim(
            x, v, i * self.chunk, axis=0), bank, rows)

    def _step_fn(self, client_fn, agg, collect, params, lbg):
        """``(acc, b_c, l_c, r_c, w_c, m_c) -> chunk_step(...)``, folding
        with ``agg.accumulate`` unless ``collect``."""
        fold = None if collect else agg.accumulate
        return lambda acc, *xs: chunk_step(client_fn, params, fold, acc,
                                           *xs)

    def _scan(self, client_fn, agg, collect, params, batch, lbg, resid, w,
              maskf):
        """The chunk step over the chunk grid in one ``lax.scan``, the
        banks updated in place in the carry. ``w``/``maskf`` are already
        padded to the grid. With ``collect`` each chunk's payload rides
        the scan outputs after the per-client metrics. Returns ``(acc,
        new_lbg, new_res, ys)``."""
        chunk = self.chunk
        n_chunks = w.shape[0] // chunk
        step = self._step_fn(client_fn, agg, collect, params, lbg)

        def body(carry, xs):
            acc, lbg_bank, res_bank = carry
            i, b_c, w_c, m_c = xs
            l_c, r_c = self._read(lbg_bank, i), self._read(res_bank, i)
            out, nl, nr, *ys = step(acc, b_c, l_c, r_c, w_c, m_c)
            if collect:
                ys.append(out)
            else:
                acc = out
            with scope(FOLD):
                lbg_bank = self._write(lbg_bank, nl, i)
                res_bank = self._write(res_bank, nr, i)
            return (acc, lbg_bank, res_bank), tuple(ys)

        with scope(FOLD):
            acc0 = jnp.zeros(()) if collect else agg.init(params)
        (acc, new_lbg, new_res), ys = jax.lax.scan(
            body, (acc0, lbg, resid),
            (jnp.arange(n_chunks), batch, w.reshape(n_chunks, chunk),
             maskf.reshape(n_chunks, chunk)))
        return acc, new_lbg, new_res, ys

    def run(self, client_fn, agg, params, batch, lbg, resid, w, maskf):
        K = self.num_clients
        w, maskf = _pad_to_grid(self.pad, w, maskf)
        # a robust rule cannot fold a median chunk-by-chunk: it gets the
        # raw per-client payloads stacked as scan outputs instead
        # (O(Kp·payload) — the documented collect-mode memory)
        collect = getattr(agg, "collect", False)
        acc, new_lbg, new_res, ys = self._scan(
            client_fn, agg, collect, params, batch, lbg, resid, w, maskf)
        # ys: (loss, uplink, scalar, wire), then the payloads if collect
        with scope(FOLD):
            out = (agg.reduce(w, jax.tree.map(_flat_clients, ys[4]))
                   if collect else agg.finalize(acc))
        per_client = lambda x: _flat_clients(x)[:K]
        return (out, new_lbg, new_res,
                *jax.tree.map(per_client, tuple(ys[:4])))


@register_scheduler("buffered")
class BufferedScheduler(ChunkedScheduler):
    """FedBuff-style buffered asynchronous aggregation (chunked layout).

    Three stages per round, one jit'd function:

    1. **compute** — :meth:`ChunkedScheduler._scan` runs :func:`chunk_step`
       on every chunk with no fold and the *dispatch* mask: state banks
       update only for dispatching clients (a client busy with an
       in-flight payload neither recomputes its bank nor re-dispatches),
       and the payloads ride the scan outputs to the buffer.
    2. **buffer write** — each dispatching client overwrites its single
       in-flight slot (payload leaves in wire layout, gscale, its
       dispatch-round weight, and uplink/scalar/wire accounting) via a
       ``where`` on the dispatch mask; everyone else's slot is carried
       bit-unchanged.
    3. **delivery fold** — the round's *delivered* slots are folded with
       weights ``w0 * disc(stale) * deliver``, normalized over the
       delivered cohort. Streaming rules fold with ``agg.accumulate``
       chunk by chunk over the same grid, in the same client order;
       collect rules get the full (Kp, ...) stack, so staleness-aware
       weighting reaches mean / geometric_median / scalar_median through
       the one weight vector they already honor.

    Delivered uplink/scalar/wire are reported in the arrival round; a
    round that delivers nothing reports zeros (the ledger guards its
    savings ratios against a zero-vanilla round).
    """

    #: engine marker: run via run_buffered with the host delivery plan
    delivery_weighted = True

    def run(self, client_fn, agg, params, batch, lbg, resid, w, maskf):
        raise TypeError(
            "BufferedScheduler aggregates through run_buffered(...); the "
            "engine threads the delivery plan and staleness buffer")

    def run_buffered(self, client_fn, agg, params, batch, lbg, resid,
                     buf, w0, dispatchf, deliverf, stalef, disc):
        """One buffered round: ``(agg_out, new_lbg, new_res, new_buf,
        losses, uplink, scalar, wire)``. The per-client accounting is each
        slot's stored value, for the caller to count over ``deliverf``."""
        K, chunk, pad = self.num_clients, self.chunk, self.pad
        w0p, dzp = _pad_to_grid(pad, w0, dispatchf)
        _, new_lbg, new_res, ys = self._scan(
            client_fn, agg, True, params, batch, lbg, resid, w0p, dzp)
        loss, uplink, scalar, wire, gt = jax.tree.map(_flat_clients, ys)

        def gate(new, old):
            d = dzp.reshape((-1,) + (1,) * (new.ndim - 1))
            return jnp.where(d > 0, new.astype(old.dtype), old)
        nbuf = jax.tree.map(gate, {
            "send": gt[0], "gscale": gt[1], "w0": w0p, "uplink": uplink,
            "scalar": scalar, "wire": wire}, buf)

        # delivery weights: stored dispatch-round weight x staleness
        # discount x delivered flag, normalized with the same (K,)
        # expression round_fn applies to the synchronous schedulers —
        # under the zero-latency plan (dispatch == deliver == mask,
        # stale == 0, disc(0) == 1.0 exactly, undelivered slots zeroed
        # by the flag) this reproduces the chunked weights bit-for-bit.
        wd = nbuf["w0"][:K] * disc(stalef) * deliverf
        wn = wd / jnp.maximum(jnp.sum(wd), 1e-12)
        (wnp,) = _pad_to_grid(pad, wn)
        payload = (nbuf["send"], nbuf["gscale"])
        with scope(FOLD):
            if getattr(agg, "collect", False):
                out = agg.reduce(wnp, payload)
            else:
                to_chunks = lambda x: x.reshape((-1, chunk) + x.shape[1:])
                acc, _ = jax.lax.scan(
                    lambda acc, xs: (agg.accumulate(acc, *xs), None),
                    agg.init(params), jax.tree.map(to_chunks, (wnp, payload)))
                out = agg.finalize(acc)
        return (out, new_lbg, new_res, nbuf, *jax.tree.map(
            lambda x: x[:K],
            (loss, nbuf["uplink"], nbuf["scalar"], nbuf["wire"])))


def pick_sharded_chunk(num_clients: int, chunk_size: int, n_dev: int) -> int:
    """Scan-block size for the sharded scheduler.

    Same policy as :func:`pick_chunk` with one extra constraint: the block
    must split evenly over the ``n_dev`` mesh devices (shard_map requires
    ``chunk % n_dev == 0``). ``n_dev == 1`` reduces to ``pick_chunk``
    exactly — that shared layout is half of what makes the 1-device sharded
    round bit-identical to the chunked one.
    """
    if n_dev == 1:
        return pick_chunk(num_clients, chunk_size)
    # cap at min(chunk_size, K) like pick_chunk (never more memory than
    # requested, no chunk mostly made of phantom clients), then round down
    # to the mesh grid — but never below n_dev, the smallest legal block
    c = max(min(chunk_size, num_clients) // n_dev * n_dev, n_dev)
    divs = [x for x in range(n_dev, c + 1, n_dev) if num_clients % x == 0]
    if divs and divs[-1] >= max(n_dev, c // 2):
        return divs[-1]
    return c


@register_scheduler("sharded")
class ShardedScheduler(ChunkedScheduler):
    """Chunked layout with each block mapped over the 2-D ``(clients,
    model)`` FL mesh: :class:`ChunkedScheduler`'s scan of
    :func:`chunk_step`, with the step run under ``shard_map`` so every
    chunk's clients train data-parallel along the ``clients`` axis
    (``FLConfig.mesh``, resolved by ``launch.mesh.make_fl_mesh``): the
    per-DEVICE transient set is O(chunk·M / n_clients_dev). With a 2-D
    spec (``mesh=[c, m]``) the LBGM decision and the sparse
    banks/aggregator carry additionally shard their block rows over the
    ``model`` axis, dropping per-device bank bytes to
    O(K·k_frac·M / (c·m)) for the >=34B-style configs where the look-back
    bank dominates memory.

    State banks are stored ``(n_chunks, chunk, ...)`` with the chunk's
    client axis sharded over the mesh — and, for a model-sharded sparse
    bank (see :meth:`configure_store` / ``bank_model_partition``), the
    block-row axis over ``model`` — so the per-chunk bank read/write and
    the LBGM accept/recycle decision touch only device-local rows; the
    cross-device traffic per chunk is one fp32 psum of the weighted
    aggregate along ``clients`` (plus loss/uplink scalars) and, when
    model-sharded, the three decision scalars psum'd along ``model``
    inside the store's step.

    The step's fold seeds client-device 0 with the scan carry and psums
    over ``clients`` (:meth:`_psum_fold`), so on a (1, 1) mesh the
    addition order — and therefore the whole round history — is
    bit-identical to ``ChunkedScheduler`` (and an ``(n, 1)`` mesh is
    bit-identical to the pre-2-D 1-D client mesh); on larger meshes the
    psum reassociates the sum across devices, the documented
    fp32-tolerance difference (uplink accounting is still exact: the
    global block layout is mesh-shape independent).
    """

    client_axis = "clients"
    model_axis = "model"

    def __init__(self, cfg: FLConfig, num_clients: int):
        from repro.launch.mesh import make_fl_mesh
        self.mesh = make_fl_mesh(cfg.mesh, client_axis=self.client_axis,
                                 model_axis=self.model_axis)
        self.n_client_dev = int(self.mesh.shape[self.client_axis])
        self.n_model = int(self.mesh.shape[self.model_axis])
        self.n_dev = int(self.mesh.devices.size)
        self.num_clients = num_clients
        self.chunk = pick_sharded_chunk(num_clients, cfg.chunk_size,
                                        self.n_client_dev)
        self.pad = (-num_clients) % self.chunk
        #: set by configure_store when the LBG bank model-shards: per-leaf
        #: {name: bool} for the sparse bank's block rows, mirrored onto
        #: the aggregator carry; None = everything model-replicated (the
        #: 1-D client-mesh behavior)
        self.model_sharded: Optional[Dict[str, bool]] = None
        #: set by bind_model_axes (model_sharding="auto"): per-leaf param
        #: NamedShardings resolved from the model component's logical
        #: axes (the engine places and keeps params with them). None =
        #: "replicate".
        self.param_shardings: Optional[Dict[str, NamedSharding]] = None
        # the global name -> (nb, block, kb) block layouts the auto chunk
        # function reshapes gradients into (bind_model_axes)
        self._layouts = None

    # ----------------------------------------------------- model binding
    def configure_store(self, store, sparse_agg: bool, params) -> None:
        """Record which bank/aggregator leaves shard over ``model``.

        Model sharding is on only when all three hold: a 2-D mesh was
        requested, the engine picked sparse aggregation (the dense
        g_tilde path cannot assemble leaves across model ranks), and the
        store knows how to partition its bank
        (``store.bank_model_partition``). Otherwise the model axis has
        extent >= 1 but everything on it is replicated — bit-for-bit the
        pre-2-D behavior.
        """
        if (self.n_model > 1 and sparse_agg
                and hasattr(store, "bank_model_partition")):
            self.model_sharded = store.bank_model_partition(params)

    def bind_model_axes(self, axes_tree, params, layouts) -> None:
        """Switch this scheduler into ``model_sharding="auto"``.

        ``axes_tree`` is the model component's logical-axis pytree (the
        ``train.sharding.params_shardings`` input — e.g. ``("embed",
        "heads")``); it is resolved against this mesh into per-leaf
        ``PartitionSpec``s with ``param_pspec`` in "replicated" mode (the
        FL mesh has no fsdp "data" axis — only the model-parallel axes
        shard, and only where the mesh extent divides). ``layouts`` is the
        engine-computed global ``name -> (nb, block, kb)`` block layout
        the auto chunk function (and the store's ``blocked_sparse_step``)
        share, so the gradient reshape and the decision slicing agree by
        construction.

        Leaves with a ``vocab`` logical axis (embedding table, lm_head)
        are sharded along their ``embed`` (d_model) dim instead of vocab:
        vocab sharding makes the token lookup and the CE label pick
        gathers *along the sharded dim*, whose backward is a scatter the
        SPMD partitioner refuses to split inside a partial-auto region
        (``Check failed: sharding.IsManualSubgroup``). d_model sharding
        keeps those gathers device-local — the only collective left is
        the contraction psum GSPMD inserts.
        """
        from repro.train.sharding import param_pspec
        missing = sorted(set(params) - set(axes_tree))
        if missing:
            raise ValueError(
                f"model_sharding='auto': the model component's axes tree "
                f"is missing leaves {missing} — every param leaf needs a "
                "logical-axis tuple (see train.sharding.params_shardings)")
        m = self.mesh.shape.get(self.model_axis, 1)

        def leaf_spec(name):
            axes = tuple(axes_tree[name])
            shape = params[name].shape
            if "vocab" in axes:
                out, used = [], False
                for logical, dim in zip(axes, shape):
                    if logical == "embed" and not used and dim % m == 0:
                        out.append(self.model_axis)
                        used = True
                    else:
                        out.append(None)
                return P(*out)
            return param_pspec(axes, shape, "replicated", self.mesh)

        self._layouts = layouts
        self.param_shardings = {
            name: NamedSharding(self.mesh, leaf_spec(name))
            for name in params}

    def _bank_leaf_spec(self, path, chunk_leading: bool):
        """PartitionSpec for one bank leaf; ``path`` is the jax key path
        ((name,) for a dense bank leaf, (name, 'idx'|'val') for a sparse
        one). ``chunk_leading=True`` adds the scan's n_chunks axis."""
        ms = self.model_sharded or {}
        name = path[0].key if path else None
        axes = (self.client_axis,)
        if len(path) == 2 and ms.get(name):
            axes = (self.client_axis, self.model_axis)
        return P(None, *axes) if chunk_leading else P(*axes)

    def _payload_specs(self, agg, lbg):
        """Collect-stack specs for the sparse payload leaves.

        Same client/model placement as the bank rows the payload came
        from, but with the codec's leaf structure (``agg.payload_keys``):
        a quantized payload carries a per-block-row ``scale`` leaf the
        bank does not have, so the bank's spec tree cannot be reused
        verbatim when the bank model-shards."""
        ms = self.model_sharded or {}
        pk = getattr(agg, "payload_keys", ("idx", "val"))
        spec = lambda name: (P(self.client_axis, self.model_axis)
                             if ms.get(name) else P(self.client_axis))
        return {name: {k: spec(name) for k in pk} for name in lbg}

    # ------------------------------------------------------ bank placement
    def layout_banks(self, bank):
        """(Kp, ...) bank -> (n_chunks, chunk, ...), client axis sharded
        (block-row axis too, for a model-sharded sparse bank).

        The round scan indexes whole chunks (axis 0), so sharding axis 1
        over the mesh puts every chunk's bank rows exactly where its
        clients train — per-chunk read/write never moves bank bytes
        between devices."""
        def f(path, x):
            x = x.reshape((x.shape[0] // self.chunk, self.chunk)
                          + x.shape[1:])
            if self.n_dev > 1:
                x = jax.device_put(x, NamedSharding(
                    self.mesh, self._bank_leaf_spec(path, True)))
            return x
        return jax.tree_util.tree_map_with_path(f, bank)

    def _read(self, bank, i):
        return jax.tree.map(
            lambda x: jax.lax.dynamic_index_in_dim(x, i, 0, keepdims=False),
            bank)

    def _write(self, bank, rows, i):
        return jax.tree.map(
            lambda x, v: jax.lax.dynamic_update_index_in_dim(x, v, i, 0),
            bank, rows)

    # ------------------------------------------------------- chunk step
    def _psum_fold(self, agg):
        """``agg.accumulate`` on each client device, summed over
        ``clients``. Client-device 0 seeds its local accumulation with
        the scan carry, so each chunk folds into the aggregate in the
        same strictly sequential order as :class:`ChunkedScheduler`; the
        psum is the identity on a 1-device client axis (the carry — dense
        params-shaped or sparse block-layout, per the aggregator — is
        replicated along ``clients``; model-sharded carry leaves hold
        disjoint rows per model rank, never summed over model)."""
        ax = self.client_axis

        def fold(acc, w_c, gt):
            first = jax.lax.axis_index(ax) == 0
            acc = jax.tree.map(lambda a: jnp.where(first, a, 0.0), acc)
            return jax.lax.psum(agg.accumulate(acc, w_c, gt), ax)

        return fold

    def _specs(self, lbg):
        """(bank-row specs, aggregator-carry specs): sparse-bank leaves
        may shard block rows over the model axis, and the carry mirrors
        the same partition (its (nb, block) leaves hold the rows the
        local sends fold into)."""
        ms = self.model_sharded
        if not ms:
            return P(self.client_axis), P()
        lbg_specs = jax.tree_util.tree_map_with_path(
            lambda path, _: self._bank_leaf_spec(path, False), lbg)
        return lbg_specs, {name: P(self.model_axis) if on else P()
                           for name, on in ms.items()}

    def _step_fn(self, client_fn, agg, collect, params, lbg):
        if self.param_shardings is not None:
            return self._auto_chunk_fn(client_fn, agg, params, lbg)
        rep, cl = P(), P(self.client_axis)
        lbg_specs, acc_specs = self._specs(lbg)
        out_specs, fold = acc_specs, None
        if collect:
            # no carry to fold: each device emits its local clients' raw
            # payloads, stitched to the global (chunk, ...) stack by the
            # out specs (sparse payloads keep the bank's client/model
            # placement; the weighted reduce runs once per round on the
            # global stack, outside shard_map)
            acc_specs, out_specs = rep, cl
            if getattr(agg, "sparse", False):
                out_specs = ((self._payload_specs(agg, lbg)
                              if self.model_sharded else cl), cl)
        else:
            fold = self._psum_fold(agg)
        local = jax.shard_map(
            lambda acc, p, *xs: chunk_step(client_fn, p, fold, acc, *xs),
            mesh=self.mesh, in_specs=(acc_specs, rep, cl, lbg_specs, cl, cl,
                                      cl),
            out_specs=(out_specs, lbg_specs, cl, cl, cl, cl, cl),
            check_vma=False)
        return lambda acc, *xs: local(acc, params, *xs)

    def _auto_chunk_fn(self, client_fn, agg, params, lbg):
        """The chunk function of ``model_sharding="auto"``.

        The per-chunk client compute (``pre``: local SGD, attack,
        pipeline) runs as plain GSPMD — no enclosing shard_map — with the
        params constrained to the component's resolved tensor-parallel
        shardings and the batch constrained along ``clients``, so the
        vmapped local-SGD forward/backward partitions over the full 2-D
        mesh. (An enclosing partial-auto shard_map is NOT an option:
        ``lax.scan`` bodies — the layer stack, tau local-SGD, chunked CE —
        trip the SPMD partitioner's manual-subgroup checks, as do
        top_k/scatter.) The decision + codec (``post``) and the fold then
        run in ONE fully-manual shard_map over (clients, model): its
        in_specs hand each rank exactly the bank/accumulator/block rows
        the "replicate" path owns, and GSPMD implements the one TP-layout
        -> block-row reshard of the round at that boundary. Banks and the
        aggregation carry keep the exact "replicate" placement and the
        global block layout is unchanged, so uplink accounting is
        identical; histories match within fp32 reassociation tolerance.
        """
        pre, post = client_fn
        mesh, ax, layouts = self.mesh, self.client_axis, self._layouts
        cl = P(ax)
        lbg_specs, acc_specs = self._specs(lbg)
        ms = self.model_sharded or {}
        blk_spec = lambda name: (P(ax, self.model_axis, None)
                                 if ms.get(name) else cl)
        fold = self._psum_fold(agg)
        cst = jax.lax.with_sharding_constraint
        along_clients = NamedSharding(mesh, cl)

        def manual_fn(acc, blk, l, cost, thru, w_c, m_c):
            gt, nl, uplink, scalar, wire = jax.vmap(post)(blk, l, cost, thru)
            with scope(FOLD):
                acc = fold(acc, w_c, gt)
                nl = _keep_sampled(m_c, nl, l)
            return acc, nl, uplink, scalar, wire

        def step(acc, b, l, r, w_c, m_c):
            p = jax.tree.map(cst, params, self.param_shardings)
            b = jax.tree.map(lambda v: cst(v, along_clients), b)
            asg, nr, loss, cost, thru = jax.vmap(
                lambda bb, rr: pre(p, bb, rr))(b, r)
            loss = jax.tree.map(lambda v: cst(v, along_clients), loss)
            blocked = {name: jax.vmap(
                lambda g, lay=layouts[name]: lbgm_lib._to_blocks(
                    g, *lay[:2]))(asg[name]) for name in asg}
            manual = jax.shard_map(
                manual_fn, mesh=mesh,
                in_specs=(acc_specs,
                          {name: blk_spec(name) for name in blocked},
                          lbg_specs, cl, jax.tree.map(lambda _: cl, thru),
                          cl, cl),
                out_specs=(acc_specs, lbg_specs, cl, cl, cl),
                check_vma=False)
            acc, nl, uplink, scalar, wire = manual(
                acc, blocked, l, cost, thru, w_c, m_c)
            with scope(FOLD):
                nr = _keep_sampled(m_c, nr, r)
            return acc, nl, nr, loss, uplink, scalar, wire

        return step


def make_scheduler(cfg: FLConfig, num_clients: int):
    """Resolve the configured client scheduler through ``SCHEDULERS``."""
    return SCHEDULERS.get(cfg.scheduler)(cfg, num_clients)


# ------------------------------------------------------------- engine

class FLEngine:
    """loss_fn(params, batch_dict) -> (loss, metrics). Data is a list of
    per-client dicts of numpy arrays (see repro.fed.partition).

    ``model_axes`` is the model component's optional logical-axis pytree
    (``{name: ("embed", "heads"), ...}`` — the
    ``train.sharding.params_shardings`` input). It is required by — and
    only read under — ``FLConfig.model_sharding="auto"``, where the
    sharded scheduler resolves it against the 2-D mesh so each client's
    local-SGD forward/backward runs tensor-parallel over the ``model``
    axis. ``fed.experiment.build_experiment`` threads it automatically
    from components that return ``(params, loss_fn, axes_tree)``.
    """

    def __init__(self, loss_fn: Callable, params: Dict[str, jax.Array],
                 client_data: List[Dict[str, np.ndarray]], flcfg: FLConfig,
                 model_axes: Optional[Dict] = None):
        self.loss_fn = loss_fn
        self.cfg = flcfg
        self.params = params
        self.model_axes = model_axes
        self.client_data = client_data
        K = flcfg.num_clients
        assert len(client_data) == K
        empty = [k for k, d in enumerate(client_data)
                 if len(next(iter(d.values()))) == 0]
        if empty:
            raise ValueError(
                f"FLEngine: clients {empty} have no training samples; "
                "every client needs >= 1 (a label-skew partition starves "
                "clients when class demand exceeds supply — use more data, "
                "fewer clients, or more classes_per_client)")
        # Byzantine attack + fault injection (repro.fed.attacks): the
        # Byzantine cohort is one fixed round(attack_frac*K) subset for the
        # whole run; data-level attacks corrupt the malicious clients'
        # local shards HERE, before the engine concatenates its one copy
        # of the dataset. Per-round attack noise and dropout_frac
        # straggler faults consume the dedicated fault stream, never the
        # batch/mask rng — a clean run is bit-for-bit unchanged.
        self.attack = make_attack(flcfg)
        self._byz = select_byzantine(K, flcfg.attack_frac, flcfg.seed)
        self._payload_attack = None
        if self.attack is not None:
            if self.attack.level == "data":
                client_data = [
                    self.attack.corrupt(d) if self._byz[k] > 0 else d
                    for k, d in enumerate(client_data)]
                self.client_data = client_data
            else:
                self._payload_attack = self.attack
        self._fault_rng = fault_rng(flcfg.seed)
        # the scheduler owns the scan-block layout (its run/prepare_batch
        # consume it); _chunk/_pad stay mirrored here as the engine's
        # introspection surface — bank padding below and the tier-1 layout
        # assertions read them
        self.sched = make_scheduler(flcfg, K)
        self._chunk, self._pad = self.sched.chunk, self.sched.pad
        sizes = np.array([len(next(iter(d.values())))
                          for d in client_data], np.float64)
        self.weights = jnp.asarray(sizes / sizes.sum(), jnp.float32)
        # per-round batch gathers run against one concatenated copy of the
        # client data (client k's samples live at offset[k]:offset[k]+n_k),
        # so _sample_batches is a single vectorized fancy-index instead of
        # a K-iteration Python loop of per-client gathers + np.stack.
        # client_data is then re-pointed at zero-copy views into the
        # concatenation so the engine holds ONE copy of the dataset.
        self._data_sizes = sizes.astype(np.int64)
        self._data_offsets = np.concatenate(
            [[0], np.cumsum(self._data_sizes[:-1])]).astype(np.int64)
        self._data_cat = {k: np.concatenate([d[k] for d in client_data])
                          for k in client_data[0]}
        self.client_data = [
            {k: v[off:off + n] for k, v in self._data_cat.items()}
            for off, n in zip(self._data_offsets, self._data_sizes)]
        self.store = make_lbg_store(flcfg)
        #: "topk-host": the LBG bank lives in host memory and run_round
        #: streams it chunk-wise (see HostTopKLBGStore / _HostBankStreamer)
        self._host_bank = bool(getattr(self.store, "host_resident", False))
        # wire codec (repro.comm.wire): payload encoding + real-byte
        # accounting. Its per-client seeds come from a dedicated stream —
        # drawn only when the codec is stochastic, so codec="none" leaves
        # the batch/mask rng (and every pre-codec history) untouched.
        self.codec = make_codec(flcfg)
        self._codec_rng = codec_rng(flcfg.seed)
        # aggregation strategy: sparse scalar-round scatter-add when the
        # store supports it and fused_kernels is not explicitly False
        self.agg, self._sparse_agg = make_aggregator(flcfg, self.store,
                                                     params, self.codec)
        # hierarchical tiers (FLConfig.tiers — repro.fed.hierarchy): wrap
        # the streaming aggregator so per-edge partial carries fold
        # alongside the untouched flat carry (finalize stays bit-for-bit
        # the flat fold). Collect-mode rules and lossy-codec payloads
        # cannot decompose over partials — for them the tier map is
        # accounting-only (per-tier ledger rows, identical numerics).
        self.tiers = make_tier_map(flcfg)
        self._tiered_fold = False
        if self.tiers is not None and type(self.agg) in (
                SparseTopKAggregator, DenseAggregator):
            self.agg = HierarchicalAggregator(
                self.agg, self.tiers.edge_ids_padded(K + self._pad),
                self.tiers.n_edges)
            self._tiered_fold = True
        if self._host_bank and getattr(self.agg, "collect", False):
            raise ValueError(
                f"lbg_variant='topk-host' streams bank chunks and folds "
                f"payloads as they arrive, but aggregator="
                f"{flcfg.aggregator!r} runs in collect mode (a full "
                "(K, payload) device stack — exactly the O(K) memory the "
                "host store exists to avoid); use aggregator='mean'")
        if self.codec.lossy and not (
                self._sparse_agg or isinstance(self.store, NullLBGStore)):
            raise ValueError(
                f"codec={flcfg.codec!r} is lossy, but the dense LBGM bank "
                "cannot track the server-decoded values (recycle rounds "
                "would replay unquantized LBGs the server never saw). Use "
                "the sparse payload path (lbg_variant='topk'/'topk-sharded' "
                "with fused_kernels not False) or vanilla FL "
                "(use_lbgm=False)")
        # buffered scheduler (FedBuff-style): latency model, host-side
        # delivery plan state, and — below, once Kp is known — the
        # device-side staleness buffer. Synchronous schedulers skip all
        # of it (attributes stay None, every code path unchanged).
        self._latency = None
        self._buffer = None
        self._tau_vec = None
        if getattr(self.sched, "delivery_weighted", False):
            if not self._sparse_agg:
                raise ValueError(
                    "scheduler='buffered' buffers sparse (idx, val) "
                    "payloads between dispatch and delivery — use "
                    "lbg_variant='topk'/'topk-sharded' and leave "
                    "fused_kernels unset or True")
            self._latency = make_latency(flcfg)
            # host delivery plan: at most one in-flight payload per
            # client; arrival[k] = the round it lands (-1 = idle)
            self._arrival = np.full(K, -1, np.int64)
            self._dispatch_round = np.zeros(K, np.int64)
            self._plan_round = 0
            self._pending_delays = None
            self._tau_vec = self._latency.sample_tau(K, flcfg.tau)
            #: delivered-payload count across the run (wire bytes are
            #: attributed per delivery — see the wire-attribution tests)
            self.n_delivered = 0.0
        # 2-D (clients, model) mesh: the scheduler decides — with the
        # store — which bank/aggregator leaves shard over the model axis,
        # BEFORE the banks are laid out below
        if hasattr(self.sched, "configure_store"):
            self.sched.configure_store(self.store, self._sparse_agg, params)
        # model_sharding="auto": validate the contract, resolve the
        # component's axes tree against the mesh, and place the params
        # model-sharded (their per-device argument buffer becomes the 1/m
        # shard). "replicate" (default) skips all of this — bit-for-bit
        # the pre-knob engine.
        self._auto_layouts = None
        if flcfg.model_sharding == "auto":
            self._setup_model_sharding(params, model_axes)
        # banks are allocated padded to the chunk grid once, up front; the
        # phantom rows stay zero forever (their mask is always 0), so the
        # per-round scan updates them in place with no pad/slice copies
        Kp = K + self._pad
        self.lbg = self.store.init(params, Kp)
        self._pipeline, self._use_ef = make_uplink_pipeline(
            flcfg.compressor, flcfg.compressor_kw, flcfg.error_feedback)
        self.residual = jax.tree.map(
            lambda p: jnp.zeros((Kp,) + p.shape, jnp.float32), params) \
            if self._use_ef else {}
        # a scheduler may own the banks' physical layout (the sharded
        # scheduler reshapes to (n_chunks, chunk, ...) and places the
        # client axis over its mesh); values are unchanged
        if hasattr(self.sched, "layout_banks"):
            self.lbg = self.sched.layout_banks(self.lbg)
            self.residual = self.sched.layout_banks(self.residual)
        if self._latency is not None:
            self._buffer = self._init_buffer(params, Kp)
        if self._host_bank:
            # out-of-core round: one jit'd chunk step (banks/batches
            # arrive per chunk from the streamer thread, donated so the
            # updated chunk reuses the uploaded buffer) + tiny jit'd
            # weight-prep / finalize helpers built from round_fn's own
            # helpers. No whole-round jit exists on this path.
            self._round = None
            self._chunk_fn = jax.jit(self._build_host_chunk_fn(),
                                     donate_argnums=(1, 2))
            self._host_prep = jax.jit(self._build_host_prep())
            self._host_final = jax.jit(self._build_host_final())
            self._streamer = _HostBankStreamer(self.lbg, self._chunk)
            # the daemon thread parks on its task queue; close it when
            # the engine is collected so tests building many engines do
            # not leak threads (the finalizer holds only the streamer)
            self._streamer_finalizer = weakref.finalize(
                self, self._streamer.close)
        else:
            # donate the LBG/residual banks (and the staleness buffer):
            # the round's new state reuses the old buffers instead of
            # allocating a second O(K·M) copy
            donate = (1, 2, 3) if self._latency is not None else (1, 2)
            self._round = jax.jit(self._build_round(),
                                  donate_argnums=donate)
        # uplink accounting lives in one place (repro.comm.accounting);
        # run_round records into it and history fields derive from it
        self.ledger = CommLedger()
        self.history: List[Dict[str, float]] = []
        #: post-round host-side state snapshot (rng streams + buffered
        #: delivery plan), captured by whichever thread draws the round —
        #: the consistency cut save_checkpoint persists (see there)
        self._host_snapshot: Optional[dict] = None

    # -------------------------------------------------------------- build
    def _setup_model_sharding(self, params, model_axes):
        """Wire ``model_sharding="auto"`` (called from ``__init__``).

        Every rejection names the fix: auto mode runs the decision and
        aggregation inside a nested manual-over-``model`` region, so it
        only composes with the sparse streaming contract, and the
        compressor pipeline (whose top-k/sign ops would hit model-sharded
        gradients in GSPMD auto-land) must stay off.
        """
        cfg = self.cfg

        def bad(msg):
            raise ValueError(f"model_sharding='auto': {msg}")

        if model_axes is None:
            bad("the model component carries no sharding metadata — only "
                "components returning (params, loss_fn, axes_tree) support "
                "tensor-parallel client compute (the 'lm' component does; "
                "fcn/cnn do not). Pass model_axes to FLEngine or use "
                "model_sharding='replicate'")
        if not hasattr(self.sched, "bind_model_axes"):
            bad(f"scheduler {cfg.scheduler!r} cannot bind model axes; use "
                "the built-in 'sharded' scheduler")
        if getattr(self.agg, "collect", False):
            bad(f"aggregator={cfg.aggregator!r} runs in collect mode, "
                "which stacks per-client payloads across the model axis; "
                "only the streaming 'mean' rule is supported")
        if not (self._sparse_agg
                and hasattr(self.store, "blocked_sparse_step")):
            bad("requires the sparse aggregation contract over the "
                "mesh-aware bank — set lbg_variant='topk-sharded' and "
                "leave fused_kernels unset or True")
        if cfg.compressor != "none":
            bad(f"compressor={cfg.compressor!r} would run its top-k/sign "
                "ops on model-sharded gradients inside the auto region; "
                "only compressor='none' is supported")
        # one global block layout, shared by the scheduler's gradient
        # reshape and the store's blocked decision — mesh-shape
        # independent, so uplink accounting matches "replicate" exactly
        self._auto_layouts = {
            name: lbgm_lib._block_layout(int(p.size), self.store.k_frac)
            for name, p in params.items()}
        self.sched.bind_model_axes(model_axes, params, self._auto_layouts)
        self.params = jax.device_put(params, self.sched.param_shardings)

    def _init_buffer(self, params, Kp):
        """The buffered scheduler's staleness buffer: one in-flight slot
        per (padded) client — payload leaves in the codec's wire layout,
        the payload's gscale, the client's dispatch-round weight, and the
        uplink/scalar/wire accounting scalars reported on delivery."""
        k_frac = self.store.k_frac
        lossy = self.codec.lossy
        val_dt = self.codec.wire_dtype if lossy else jnp.float32
        send = {}
        for name, leaf in params.items():
            nb, _, kb = lbgm_lib._block_layout(int(leaf.size), k_frac)
            sk = {"idx": jnp.zeros((Kp, nb, kb), jnp.int32),
                  "val": jnp.zeros((Kp, nb, kb), val_dt)}
            if "scale" in self.codec.payload_keys:
                sk["scale"] = jnp.ones((Kp, nb, 1), jnp.float32)
            send[name] = sk
        zk = lambda: jnp.zeros(Kp, jnp.float32)
        return {"send": send, "gscale": zk(), "w0": zk(),
                "uplink": zk(), "scalar": zk(), "wire": zk()}

    def _make_client_update(self, hetero_tau: bool = False):
        cfg = self.cfg
        loss_fn = self.loss_fn

        # the gradient sum rides in the scan carry: one gradient-sized
        # buffer, where stacking the tau gradients for a sum after the
        # scan held tau of them
        zero = lambda params: jax.tree.map(jnp.zeros_like, params)

        if not hetero_tau:
            def client_update(params, batches):
                """tau local steps; batches: dict leaves (tau, b, ...).
                -> (summed gradient, {"loss": mean loss, counters summed
                over the steps})."""
                def step(carry, bt):
                    p, asg = carry
                    (l, aux), g = jax.value_and_grad(loss_fn,
                                                     has_aux=True)(p, bt)
                    p2 = jax.tree.map(
                        lambda x, gg: x - cfg.lr * gg.astype(x.dtype),
                        p, g)
                    return (p2, jax.tree.map(jnp.add, asg, g)), (
                        l, _counters(aux))
                (_, asg), (ls, cs) = jax.lax.scan(
                    step, (params, zero(params)), batches)
                return asg, {"loss": jnp.mean(ls),
                             **jax.tree.map(lambda c: jnp.sum(c, 0), cs)}

            return client_update

        def client_update(params, batches, tau_k):
            """Variable-tau local SGD (buffered compute heterogeneity):
            the scan still runs the static ``cfg.tau`` steps — same
            shapes, same jit — but steps ``i >= tau_k`` are masked to
            no-ops (zero gradient, frozen params), so a slow client's
            accumulated update and reported loss cover exactly its
            ``tau_k`` real steps."""
            def step(carry, xt):
                p, asg = carry
                i, bt = xt
                (l, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    p, bt)
                on = (i < tau_k).astype(jnp.float32)
                g = jax.tree.map(lambda gg: gg * on.astype(gg.dtype), g)
                p2 = jax.tree.map(
                    lambda x, gg: x - cfg.lr * gg.astype(x.dtype), p, g)
                cs = jax.tree.map(lambda c: c * on, _counters(aux))
                return (p2, jax.tree.map(jnp.add, asg, g)), (l, on, cs)
            (_, asg), (ls, ons, cs) = jax.lax.scan(
                step, (params, zero(params)), (jnp.arange(cfg.tau), batches))
            loss = jnp.sum(ls * ons) / jnp.maximum(jnp.sum(ons), 1.0)
            return asg, {"loss": loss,
                         **jax.tree.map(lambda c: jnp.sum(c, 0), cs)}

        return client_update

    def _sparse_encoder(self):
        """``codec.encode_sparse`` for this engine's bank placement.

        Where the sharded scheduler splits a leaf's block rows over the
        model axis, each model rank encodes only its rows, so the codec's
        byte count covers a 1/n_model share of that leaf: sum those
        leaves' full-round bytes over the model axis (replicated leaves
        count once), so every mesh shape reports the bytes one client
        ships."""
        codec = self.codec
        sharded = getattr(self.sched, "model_sharded", None) or {}
        if not any(sharded.values()):
            return codec.encode_sparse
        axis = self.sched.model_axis

        def encode(out, new_lbg, stats, seed):
            out, new_lbg, _ = codec.encode_sparse(out, new_lbg, stats, seed)
            part = lambda on: {n: s for n, s in out[0].items()
                               if sharded[n] == on}
            full = (jax.lax.psum(codec.sparse_full_bytes(part(True)), axis)
                    + codec.sparse_full_bytes(part(False)))
            return out, new_lbg, jnp.where(stats.sent_scalar,
                                           codec.scalar_bytes, full)

        return encode

    def _client_halves(self, step):
        """``client_fn`` in two halves, split at the decision seam.

        ``pre(params, batches, resid_k) -> (asg, resid_k, loss, cost,
        thru)`` strips the engine-reserved batch keys and runs local SGD,
        the attack and the uplink pipeline; ``thru`` carries the
        per-client codec seed on to ``post(asg, lbg_k, cost, thru) ->
        (gt, lbg_k, uplink, sent_scalar, wire)``, which runs the store's
        decision ``step``, the uplink count and the codec encode."""
        pipeline = self._pipeline
        store = self.store
        hetero_tau = self._tau_vec is not None
        client_update = self._make_client_update(hetero_tau)
        sparse = self._sparse_agg
        attack = self._payload_attack
        codec = self.codec
        encode = self._sparse_encoder()
        # the legacy dense-aggregation oracle over a top-k store ships the
        # same conceptual (idx, val) payload as the sparse path, so its
        # wire bytes come from the store's static block layout — the two
        # paths must report identical histories (codec is lossless here:
        # lossy codecs are rejected at __init__ without sparse agg)
        sparse_wire = None
        if not sparse and getattr(store, "k_frac", None) is not None:
            sparse_wire = codec.sparse_layout_bytes(
                [lbgm_lib._block_layout(int(p.size), store.k_frac)[::2]
                 for p in self.params.values()])

        def pre(params, batches, resid_k):
            # engine-reserved batch keys (Byzantine flag, per-round attack
            # extras, per-client wire-codec seed, local-step budget) ride
            # the batch dict through every scheduler layout and the
            # prefetcher; strip them before the local-SGD scan
            batches = dict(batches)
            byz = batches.pop(BYZ_KEY, None)
            thru = {}
            if WIRE_KEY in batches:
                thru[WIRE_KEY] = batches.pop(WIRE_KEY)
            tau_k = batches.pop(TAU_KEY, None)
            extras = {k: batches.pop(k) for k in list(batches)
                      if k.startswith("_atk_")}
            with scope(CLIENT_SGD):
                if hetero_tau:
                    asg, loss = client_update(params, batches, tau_k)
                else:
                    asg, loss = client_update(params, batches)
            if attack is not None:
                # the Byzantine client corrupts its accumulated gradient
                # BEFORE the uplink pipeline and the LBGM decision: its
                # bank, accept/recycle choice and payload all follow from
                # the corrupted update, exactly as a protocol-following
                # adversary would produce them
                asg = attack.apply(asg, byz, extras)
            asg, resid_k, cost = pipeline(asg, resid_k)
            return asg, resid_k, loss, cost, thru

        def post(asg, lbg_k, cost, thru):
            # sparse aggregation: gt is the ((idx, val) payload, gscale)
            # pair the sparse aggregators fold — the dense g_tilde is
            # never materialized
            with scope(DECISION):
                gt, lbg_k, stats = step(asg, lbg_k)
                # scalar rounds upload 1 float; full rounds pay the base
                # cost
                uplink = jnp.where(stats.sent_scalar, 1.0,
                                   store.full_round_cost(cost, stats))
            # wire codec: encode the payload the uplink actually ships
            # (and, for lossy codecs, re-point the bank at the values the
            # server will decode) + account the real bytes on the wire
            with scope(CODEC):
                wire_seed = thru.get(WIRE_KEY)
                if sparse:
                    gt, lbg_k, wire = encode(gt, lbg_k, stats, wire_seed)
                elif sparse_wire is not None:
                    wire = jnp.where(stats.sent_scalar, codec.scalar_bytes,
                                     sparse_wire)
                else:
                    gt, wire = codec.encode_dense(gt, uplink, wire_seed)
            return gt, lbg_k, uplink, stats.sent_scalar, wire

        return pre, post

    def _build_client_fn(self):
        """One client's round, ``post(pre(...))`` with the store's
        decision step: ``(gt, new_lbg, new_resid, loss, uplink,
        sent_scalar, wire)``."""
        store = self.store
        pre, post = self._client_halves(
            store.sparse_client_step if self._sparse_agg
            else store.client_step)

        def client_fn(params, batches, lbg_k, resid_k):
            asg, resid_k, loss, cost, thru = pre(params, batches, resid_k)
            gt, lbg_k, uplink, scalar, wire = post(asg, lbg_k, cost, thru)
            return gt, lbg_k, resid_k, loss, uplink, scalar, wire

        return client_fn

    def _build_round(self):
        cfg, sched, aggregator = self.cfg, self.sched, self.agg
        pshard = getattr(sched, "param_shardings", None)
        if pshard is not None:
            # model_sharding="auto": the scheduler runs ``pre`` in the
            # GSPMD region and ``post`` in the manual-over-``model``
            # region, on gradients already in block-row layout
            client_fn = self._client_halves(
                self.store.blocked_sparse_step(self._auto_layouts))
        else:
            client_fn = self._build_client_fn()

        if self._latency is not None:
            disc = self._latency.staleness_weight

            def round_fn(params, lbg, residual, buf, batch, dispatch,
                         deliver, stale):
                """Buffered delivery-time round. ``dispatch`` /
                ``deliver`` / ``stale`` are the host plan's (K,) vectors
                (see ``_sample_mask``); ``buf`` is the staleness buffer.
                Loss is reported over the round's *computing* (dispatch)
                cohort; uplink/scalar/wire over the *delivered* payloads
                — bytes land in the round they arrive."""
                dispatchf = dispatch.astype(jnp.float32)
                deliverf = deliver.astype(jnp.float32)
                stalef = stale.astype(jnp.float32)
                w0 = self.weights * dispatchf
                wl = w0 / jnp.maximum(jnp.sum(w0), 1e-12)
                (agg_out, new_lbg, new_res, new_buf, losses, uplink,
                 scalar, wire) = sched.run_buffered(
                    client_fn, aggregator, params, batch, lbg, residual,
                    buf, w0, dispatchf, deliverf, stalef, disc)
                new_params = server_step(cfg.lr, params, agg_out)
                metrics = round_metrics(losses, wl, uplink, scalar, wire,
                                        deliverf, dispatchf)
                return new_params, new_lbg, new_res, new_buf, metrics

            return round_fn

        def round_fn(params, lbg, residual, batch, mask):
            """batch leaves: scheduler layout (see prepare_batch);
            mask: (K,) participation. In chunked mode the state banks are
            permanently padded to the chunk grid (zero-weight phantom
            clients, always masked out); the scheduler pads the small
            per-round vectors itself."""
            maskf, w = round_weights(self.weights, mask)
            agg, new_lbg, new_res, losses, uplink, scalar, wire = sched.run(
                client_fn, aggregator, params, batch, lbg, residual, w,
                maskf)
            new_params = server_step(cfg.lr, params, agg)
            if pshard is not None:
                # keep the updated params on their TP layout round over
                # round (the donated input buffers are reused in place)
                new_params = jax.tree.map(
                    jax.lax.with_sharding_constraint, new_params, pshard)
            metrics = round_metrics(losses, w, uplink, scalar, wire, maskf)
            return new_params, new_lbg, new_res, metrics

        return round_fn

    # ------------------------------------------------ out-of-core (host)
    def _build_host_chunk_fn(self):
        """One chunk of the topk-host round: :func:`chunk_step` with the
        aggregator's fold, compiled standalone so the only
        device-resident bank state is the active chunk's rows. The store
        keeps no residual (no error feedback), so the chunk's new bank
        rows are the only state written back. ``acc`` and ``lbg_c`` are
        donated: the updated chunk reuses the uploaded buffer."""
        client_fn = self._build_client_fn()
        fold = self.agg.accumulate

        def chunk_fn(params, acc, lbg_c, resid_c, b_c, w_c, m_c):
            acc, nl, _, loss, uplink, scalar, wire = chunk_step(
                client_fn, params, fold, acc, b_c, lbg_c, resid_c, w_c, m_c)
            return acc, nl, loss, uplink, scalar, wire

        return chunk_fn

    def _build_host_prep(self):
        """Round weights for the host chunk loop, as ``round_fn`` and
        the chunked scheduler compute them."""
        def prep(mask):
            maskf, w = round_weights(self.weights, mask)
            wp, mp = _pad_to_grid(self._pad, w, maskf)
            return (wp.reshape(-1, self._chunk), mp.reshape(-1, self._chunk),
                    w, maskf)

        return prep

    def _build_host_final(self):
        """Params update + round metrics over the concatenated per-chunk
        outputs, as ``round_fn`` computes them."""
        def final(params, acc, losses, uplink, scalar, wire, w, maskf):
            with scope(FOLD):
                out = self.agg.finalize(acc)
            new_params = server_step(self.cfg.lr, params, out)
            return new_params, round_metrics(losses, w, uplink, scalar,
                                             wire, maskf)

        return final

    def _run_host_round(self, batch, mask):
        """The topk-host round loop: double-buffered bank streaming.

        The streamer thread uploads chunk ``c+1``'s bank + batch rows
        while the device computes chunk ``c`` (dispatch is async — the
        jit call returns before compute finishes), and the same thread
        writes chunk ``c``'s updated rows back to the host array (its
        ``np.asarray`` is what synchronizes on the chunk's compute).
        Device bank footprint: the in-flight chunks only — independent
        of K.
        """
        K = self.cfg.num_clients
        n_chunks = (K + self._pad) // self._chunk
        w_cs, m_cs, w, maskf = self._host_prep(
            jnp.asarray(mask, jnp.float32))
        acc = self.agg.init(self.params)
        st = self._streamer
        st.begin_round(batch, n_chunks)
        outs = []
        try:
            for c in range(n_chunks):
                lbg_c, b_c = st.get(c)
                acc, nl, loss, uplink, scalar, wire = self._chunk_fn(
                    self.params, acc, lbg_c, {}, b_c, w_cs[c], m_cs[c])
                st.put_writeback(c, nl)
                st.request(c + 2)
                outs.append((loss, uplink, scalar, wire))
        finally:
            # barrier: every write-back has landed, so self.lbg (the
            # host array) is the post-round bank when this returns
            st.finish_round()
        cat = lambda xs: jax.tree.map(
            lambda *x: jnp.concatenate(x)[:K], *xs)
        loss, uplink, scalar, wire = (cat(x) for x in zip(*outs))
        self.params, metrics = self._host_final(
            self.params, acc, loss, uplink, scalar, wire, w, maskf)
        return metrics

    def host_chunk_device_bytes(self) -> int:
        """Device bytes one streamed bank chunk occupies — the per-round
        device bank envelope is ~2x this (double buffer), independent of
        ``num_clients``."""
        if not self._host_bank:
            raise ValueError("host_chunk_device_bytes: engine does not "
                             "run the topk-host store")
        return int(sum(v.nbytes // v.shape[0]
                       for v in jax.tree.leaves(self.lbg)) * self._chunk)

    # -------------------------------------------------------------- data
    def _sample_batches(self, rng: np.random.RandomState):
        """Per-round client batches, laid out by the scheduler's
        ``prepare_batch`` (vmap: (K, tau, b, ...); chunked:
        (n_chunks, chunk, tau, b, ...), padded host-side) and staged on
        the device. This is the host half of the round hot path that
        :class:`RoundPrefetcher` overlaps with device execution.
        """
        with span(HOST_PREP):
            stacked = self._draw_batches(rng)
        if self._host_bank:
            # out-of-core path: batches stay host-side — the bank
            # streamer uploads each chunk's rows next to its bank rows,
            # so device batch bytes are O(chunk), K-independent, and the
            # prefetch thread never stages an O(K) H2D transfer
            return {k: np.asarray(v) for k, v in stacked.items()}
        with span(H2D):
            return {k: jnp.asarray(v) for k, v in stacked.items()}

    def _draw_batches(self, rng: np.random.RandomState):
        """The round's draws and gathered batch in the scheduler's layout,
        as host arrays.

        The K per-client index draws stay sequential — the rng stream is
        part of the reproducibility contract (identical draws to the
        original per-client loop) — but materialization is ONE vectorized
        fancy-index per data key from the concatenated client data
        straight into the (K, tau, b, ...) buffer: no per-client gather
        loop, no intermediate list + ``np.stack`` copy.
        """
        cfg = self.cfg
        idx = np.empty((cfg.num_clients, cfg.tau, cfg.batch_size), np.int64)
        for k, n in enumerate(self._data_sizes):
            idx[k] = rng.randint(0, n, size=(cfg.tau, cfg.batch_size))
        idx += self._data_offsets[:, None, None]
        stacked = {k: v[idx] for k, v in self._data_cat.items()}
        if self._payload_attack is not None:
            # per-client Byzantine flags (+ any per-round attack extras,
            # drawn from the fault stream — never from ``rng``) ride the
            # batch dict so they inherit the scheduler layout, the H2D
            # staging and the prefetch overlap for free
            stacked[BYZ_KEY] = self._byz
            stacked.update(self._payload_attack.round_extras(
                self._fault_rng, cfg.num_clients))
        if self.codec.stochastic:
            # per-client stochastic-rounding seeds from the dedicated
            # codec stream (never the batch/mask rng): one uint32 per
            # client per round, riding the batch layout like the fault
            # keys above. Deterministic codecs draw nothing — the stream
            # (and the prefetcher's behavior) is bit-for-bit unchanged.
            stacked[WIRE_KEY] = self._codec_rng.randint(
                0, 2 ** 31 - 1, size=cfg.num_clients).astype(np.uint32)
        if self._latency is not None:
            # buffered: this round's per-client delay draws happen HERE —
            # an adaptive attack reads its own delay (STALE_KEY) from the
            # batch dict, and _sample_mask (always called right after
            # this, on both the sync and prefetch paths) consumes the
            # cached vector to build the dispatch/deliver plan. The
            # fault-stream order per round is fixed: attack extras, then
            # delays, then dropout draws — so the async replay is
            # seed-exact.
            d = np.asarray(self._latency.sample_delays(
                self._fault_rng, cfg.num_clients), np.int64)
            self._pending_delays = d
            if self._payload_attack is not None:
                stacked[STALE_KEY] = d.astype(np.float32)
            if self._tau_vec is not None:
                stacked[TAU_KEY] = np.asarray(self._tau_vec, np.int32)
        return self.sched.prepare_batch(stacked)

    def _sample_mask(self, rng: np.random.RandomState) -> np.ndarray:
        """Algorithm-3 participation mask for one round.

        Consumes exactly ``num_clients`` uniforms from ``rng`` when
        ``sample_frac < 1`` (and none otherwise) on EVERY path: the
        empty-cohort fallback reuses the uniforms already in hand (the
        client closest to its sampling threshold) instead of drawing extra
        state, so one unlucky round cannot shift every subsequent round's
        batch/mask stream.
        """
        cfg = self.cfg
        if cfg.sample_frac >= 1.0:
            mask = np.ones(cfg.num_clients)
        else:
            u = rng.rand(cfg.num_clients)
            mask = (u < cfg.sample_frac).astype(np.float64)
            if mask.sum() == 0:
                mask[int(np.argmin(u))] = 1.0
        if cfg.dropout_frac > 0.0:
            # straggler/dropout fault injection rides the participation
            # mask: each sampled client independently fails to report with
            # prob dropout_frac. Draws come from the fault stream (exactly
            # num_clients uniforms per round, sampled or not), so the
            # Algorithm-3 rng stream above is untouched and the fault
            # pattern replays under the same seed.
            d = self._fault_rng.rand(cfg.num_clients)
            dropped = mask * (d >= cfg.dropout_frac)
            if dropped.sum() == 0:
                # an all-straggler round still needs one reporter: revive
                # the sampled client least likely to have dropped (no
                # extra draws — stream invariance, as in the empty-cohort
                # fallback above)
                dropped = np.zeros_like(mask)
                dropped[int(np.argmax(np.where(mask > 0, d, -1.0)))] = 1.0
            mask = dropped
        if self._latency is None:
            return mask
        # buffered: turn the participation mask into a delivery plan.
        # dispatch = sampled & idle (one in-flight slot per client); a
        # dispatched payload arrives `delay` rounds later and is folded,
        # staleness-discounted, in its arrival round. Pure integer host
        # bookkeeping over the already-drawn delays — no extra rng.
        t = self._plan_round
        self._plan_round += 1
        d = self._pending_delays
        if d is None:
            # mask drawn without a preceding _sample_batches (tests /
            # external drivers): draw the delays now — same stream, same
            # per-round order
            d = np.asarray(self._latency.sample_delays(
                self._fault_rng, self.cfg.num_clients), np.int64)
        self._pending_delays = None
        # max-staleness eviction (latency_kw={"max_staleness": s}): an
        # in-flight payload older than s rounds is dropped — its slot
        # frees up and the client may re-dispatch THIS round (the only
        # exit for a straggler drop=True payload parked at NEVER). Pure
        # host bookkeeping; the count lands in CommLedger.n_evicted.
        n_evicted = 0
        s_max = self._latency.max_staleness
        if s_max is not None:
            evict = (self._arrival >= 0) & \
                (t - self._dispatch_round > s_max)
            n_evicted = int(evict.sum())
            self._arrival[evict] = -1
        dispatch = (mask > 0) & (self._arrival < 0)
        self._dispatch_round[dispatch] = t
        self._arrival[dispatch] = t + d[dispatch]
        deliver = self._arrival == t
        stale = np.where(deliver, t - self._dispatch_round, 0)
        self._arrival[deliver] = -1
        return {"mask": mask,
                "dispatch": dispatch.astype(np.float64),
                "deliver": deliver.astype(np.float64),
                "stale": stale.astype(np.float64),
                "n_evicted": float(n_evicted)}

    # -------------------------------------------------------------- run
    def prefetcher(self, rng: np.random.RandomState,
                   depth: int = 2) -> "RoundPrefetcher":
        """Double-buffered host batch prep over ``rng``'s draw stream.

        Pass the returned object to :meth:`run_round` in place of the rng;
        while it is alive it must be the ONLY consumer of ``rng`` (that is
        what keeps the stream identical to the synchronous path). Call
        ``close()`` when done — it stops the thread; the rng has then been
        advanced by up to ``depth`` + 1 prefetched rounds.
        """
        return RoundPrefetcher(self, rng, depth=depth)

    def run_round(self, rng) -> Dict[str, float]:
        """One FL round. ``rng`` is either a ``np.random.RandomState``
        (synchronous host prep) or a :class:`RoundPrefetcher` (batches and
        mask already staged by the prefetch thread — same draw stream)."""
        if isinstance(rng, RoundPrefetcher):
            # the producer thread snapshots its post-draw host state with
            # every item (see _capture_host_state) — holding it here
            # means save_checkpoint always persists the state matching
            # the round that actually ran, even though the prefetch
            # thread has drawn ahead
            batch, mask, snap = rng.next()
            self._host_snapshot = snap
        else:
            batch = self._sample_batches(rng)
            mask = self._sample_mask(rng)
            self._host_snapshot = self._capture_host_state(rng)
        if isinstance(mask, dict):
            # buffered delivery plan: uplink/wire (and the vanilla
            # baseline) are attributed to the round payloads ARRIVE in,
            # so a straggler's bytes land when the server folds them
            plan = mask
            (self.params, self.lbg, self.residual, self._buffer,
             metrics) = self._round(
                self.params, self.lbg, self.residual, self._buffer,
                batch, jnp.asarray(plan["dispatch"], jnp.float32),
                jnp.asarray(plan["deliver"], jnp.float32),
                jnp.asarray(plan["stale"], jnp.float32))
            n_del = float(plan["deliver"].sum())
            self.n_delivered += n_del
            self.ledger.n_evicted += plan.get("n_evicted", 0.0)
            vanilla = n_del * tree_size(self.params)
        elif self._host_bank:
            metrics = self._run_host_round(batch, mask)
            vanilla = float(mask.sum()) * tree_size(self.params)
        else:
            self.params, self.lbg, self.residual, metrics = self._round(
                self.params, self.lbg, self.residual, batch,
                jnp.asarray(mask, jnp.float32))
            vanilla = float(mask.sum()) * tree_size(self.params)
        with span(READBACK):
            m = {k: float(v) for k, v in metrics.items()}
        tiers = None
        if self.tiers is not None:
            # per-tier wire attribution: edge links carried this round's
            # client payloads (delivered ones, under the buffered plan);
            # each active edge/region forwards one dense partial carry
            active = (plan["deliver"] if isinstance(mask, dict) else mask)
            tiers = self.tiers.round_bytes(
                active, m["wire_bytes"],
                carry_bytes=4.0 * tree_size(self.params))
        # vanilla wire = dense fp32, 4 bytes per param per participant —
        # the baseline both the float and byte savings are measured from
        self.ledger.record(m["uplink_floats"], vanilla,
                           wire=m["wire_bytes"], vanilla_wire=4.0 * vanilla,
                           tiers=tiers)
        m["total_uplink"] = self.ledger.uplink_floats
        m["vanilla_uplink"] = self.ledger.vanilla_floats
        m["savings"] = self.ledger.savings
        m["total_wire_bytes"] = self.ledger.wire_bytes
        m["wire_savings"] = self.ledger.wire_savings
        self.history.append(m)
        return m

    # engine-level accounting views derive from the ledger — the duplicate
    # hand-rolled counters (and their divergent savings guard) are gone
    @property
    def total_uplink(self) -> float:
        return self.ledger.uplink_floats

    @property
    def vanilla_uplink(self) -> float:
        return self.ledger.vanilla_floats

    # ----------------------------------------------------- checkpointing
    @staticmethod
    def _rng_state(rng: np.random.RandomState) -> dict:
        _, keys, pos, has_gauss, cached = rng.get_state()
        return {"keys": keys.copy(), "pos": np.int64(pos),
                "has_gauss": np.int64(has_gauss),
                "cached": np.float64(cached)}

    @staticmethod
    def _set_rng_state(rng: np.random.RandomState, s: dict) -> None:
        rng.set_state(("MT19937", np.asarray(s["keys"], np.uint32),
                       int(s["pos"]), int(s["has_gauss"]),
                       float(s["cached"])))

    def _capture_host_state(self, rng: np.random.RandomState) -> dict:
        """Post-round snapshot of every host-side stream that feeds the
        round draws: the batch/mask rng, the dedicated fault and codec
        streams, and the buffered delivery-plan state. Captured by
        whichever thread samples the round (the prefetch producer, or
        the sync ``run_round`` caller) right after its draws — that is
        the consistency cut that makes resume bit-for-bit: a prefetcher
        may have drawn several rounds ahead at save time, but the
        snapshot the engine holds always matches the round that actually
        executed, and the thrown-away queued draws are simply re-drawn
        identically from the restored stream.
        """
        s = {"rng": self._rng_state(rng),
             "fault_rng": self._rng_state(self._fault_rng),
             "codec_rng": self._rng_state(self._codec_rng)}
        if self._latency is not None:
            s["arrival"] = self._arrival.copy()
            s["dispatch_round"] = self._dispatch_round.copy()
            s["plan_round"] = np.int64(self._plan_round)
        return s

    def save_checkpoint(self, path: str) -> None:
        """Atomically persist the run state after the last completed
        round: params, LBG/residual banks (host array for topk-host),
        the buffered in-flight slots, all rng streams, the CommLedger,
        and the round history — everything ``restore_checkpoint`` needs
        to continue the run bit-for-bit (see ``FLConfig.ckpt_every``)."""
        if self._host_snapshot is None:
            raise ValueError(
                "save_checkpoint: no completed round to snapshot — run "
                "at least one round first")
        state = {
            "params": self.params,
            "lbg": self.lbg,
            "residual": self.residual,
            "host": self._host_snapshot,
            "ledger": self.ledger.state_dict(),
            "history": self.history,
        }
        if self._buffer is not None:
            state["buffer"] = self._buffer
            state["n_delivered"] = np.float64(self.n_delivered)
        ckpt_lib.save_checkpoint(path, state, metadata={
            "version": 1, "round": len(self.history),
            "config": self.cfg.to_dict()})

    def restore_checkpoint(self, path: str,
                           rng: np.random.RandomState) -> int:
        """Load ``path`` into this engine (built from the SAME FLConfig
        — checked against the checkpoint metadata) and restore ``rng``,
        the caller's batch/mask RandomState that will drive subsequent
        rounds. Device arrays are re-placed onto their current shardings
        (topk-sharded bank placement survives); the topk-host bank is
        restored in place so the streamer thread keeps its reference.
        Returns the number of completed rounds (the index to resume
        from)."""
        tree, meta = ckpt_lib.load_checkpoint(path)
        if meta.get("config") != self.cfg.to_dict():
            raise ValueError(
                "restore_checkpoint: checkpoint was written under a "
                "different FLConfig — rebuild the engine with the "
                f"original config. Checkpoint config: {meta.get('config')}")

        def _like(cur, new):
            return jax.device_put(np.asarray(new).astype(cur.dtype),
                                  getattr(cur, "sharding", None))

        self.params = jax.tree.map(_like, self.params, tree["params"])
        if self._host_bank:
            def copy(dst, src):
                dst[...] = np.asarray(src).astype(dst.dtype)
            jax.tree.map(copy, self.lbg, tree.get("lbg", {}))
        elif "lbg" in tree:
            self.lbg = jax.tree.map(_like, self.lbg, tree["lbg"])
        if "residual" in tree:
            self.residual = jax.tree.map(_like, self.residual,
                                         tree["residual"])
        if self._buffer is not None:
            self._buffer = jax.tree.map(_like, self._buffer,
                                        tree["buffer"])
            self.n_delivered = float(tree["n_delivered"])
        host = tree["host"]
        self._set_rng_state(rng, host["rng"])
        self._set_rng_state(self._fault_rng, host["fault_rng"])
        self._set_rng_state(self._codec_rng, host["codec_rng"])
        if self._latency is not None:
            self._arrival[...] = np.asarray(host["arrival"], np.int64)
            self._dispatch_round[...] = np.asarray(
                host["dispatch_round"], np.int64)
            self._plan_round = int(host["plan_round"])
            self._pending_delays = None
        self.ledger.load_state(tree["ledger"])
        self.history = [{k: float(v) for k, v in h.items()}
                        for h in tree.get("history", [])]
        self._host_snapshot = host
        return int(meta["round"])

    def run(self, rounds: int, eval_fn: Optional[Callable] = None,
            eval_every: int = 10, verbose: bool = False,
            prefetch: bool = True, resume: bool = False):
        cfg = self.cfg
        rng = np.random.RandomState(cfg.seed + 1)
        start = 0
        if resume:
            if not cfg.ckpt_path:
                raise ValueError(
                    "run(resume=True) needs FLConfig.ckpt_path")
            start = self.restore_checkpoint(cfg.ckpt_path, rng)
        # host batch prep for round t+1 overlaps device execution of
        # round t; numerically invisible (same rng stream, same data)
        src = self.prefetcher(rng) if prefetch else rng
        try:
            for r in range(start, rounds):
                m = self.run_round(src)
                if eval_fn is not None and (r + 1) % eval_every == 0:
                    m.update(eval_fn(self.params))
                if verbose and (r + 1) % eval_every == 0:
                    print(f"round {r+1:4d} " +
                          " ".join(f"{k}={v:.4g}" for k, v in m.items()))
                if cfg.ckpt_every and (r + 1) % cfg.ckpt_every == 0:
                    self.save_checkpoint(cfg.ckpt_path)
        finally:
            if prefetch:
                src.close()
        return self.history


# ------------------------------------------------------------- prefetcher

class RoundPrefetcher:
    """Host->device double buffering for the round loop (the ROADMAP's
    "async round overlap" item).

    A daemon thread draws each round's ``(batch, mask)`` from the engine's
    rng IN ROUND ORDER (batches first, then the participation mask —
    exactly the synchronous ``run_round`` order), tags the item with the
    post-draw host-state snapshot checkpointing relies on, and stages the
    device transfers, so round t+1's host prep and H2D copies overlap the
    device executing round t. While the prefetcher is alive it is the rng's only
    consumer, so every number in the round history is bit-identical to the
    synchronous path; the only observable difference is that ``close()``
    leaves the rng advanced by the rounds still sitting in the buffer.
    """

    _SENTINEL = object()

    def __init__(self, engine: "FLEngine", rng: np.random.RandomState,
                 depth: int = 2):
        self._engine = engine
        self._rng = rng
        self._q: queue.Queue = queue.Queue(maxsize=max(1, depth))
        self._stop = threading.Event()
        self._err: Optional[BaseException] = None
        self._thread = threading.Thread(
            target=self._produce, name="fl-round-prefetch", daemon=True)
        self._thread.start()

    def _produce(self):
        try:
            while not self._stop.is_set():
                batch = self._engine._sample_batches(self._rng)
                # re-check between the two rng draws: a close() racing this
                # loop must not trigger another _sample_mask -> H2D staging
                # round against an engine that is already tearing down
                if self._stop.is_set():
                    break
                mask = self._engine._sample_mask(self._rng)
                # the post-draw host-state snapshot travels with the item
                # (see FLEngine._capture_host_state): run_round keeps the
                # one matching the round it executes, so a checkpoint cut
                # under prefetch is exact
                item = (batch, mask,
                        self._engine._capture_host_state(self._rng))
                while not self._stop.is_set():
                    try:
                        self._q.put(item, timeout=0.05)
                        break
                    except queue.Full:
                        continue
        except BaseException as e:  # re-raised on the consumer side
            self._err = e
            while not self._stop.is_set():
                try:
                    self._q.put(self._SENTINEL, timeout=0.05)
                    break
                except queue.Full:
                    continue

    def next(self):
        """The next round's (batch, mask, snapshot); raises if the
        thread died.

        Once the producer has failed, every subsequent call re-raises
        immediately (the sentinel is posted once; without the dead flag a
        retry would block forever on the empty queue), and calling after
        ``close()`` errors instead of deadlocking on the dead producer.

        The wait itself is a timeout-loop ``get`` that re-checks
        ``_stop``/``_err`` every lap: the one-shot pre-checks above are not
        atomic with a blocking ``get()``, so a ``close()`` (or producer
        death) that lands after the checks but before the dequeue used to
        park this thread on an empty queue forever."""
        with span(PREFETCH_WAIT):
            while True:
                if self._err is not None and self._q.empty():
                    raise RuntimeError(
                        "round prefetch thread failed") from self._err
                # not "and empty": a put() blocked on a full queue while
                # close() drained it lands afterwards, leaving a stale item
                # behind
                if self._stop.is_set():
                    raise RuntimeError("RoundPrefetcher used after close()")
                try:
                    item = self._q.get(timeout=0.05)
                except queue.Empty:
                    continue
                if item is self._SENTINEL:
                    raise RuntimeError(
                        "round prefetch thread failed") from self._err
                return item

    def close(self):
        self._stop.set()
        while True:  # drain so a blocked put() observes the stop flag
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=10)
        if self._thread.is_alive():
            # a silent failed join leaks a thread that still owns the rng
            # and may touch a torn-down engine; surface it instead
            warnings.warn(
                "RoundPrefetcher thread did not exit within 10s of close(); "
                "it may be wedged in a device transfer",
                RuntimeWarning, stacklevel=2)


# ----------------------------------------------------- host bank streamer

class _HostBankStreamer:
    """Daemon thread streaming host-resident LBG bank chunks (the
    ``"topk-host"`` store) through a double buffer.

    One FIFO task queue serializes three operations:

    * ``("up", c)`` — ``jax.device_put`` chunk ``c``'s bank rows
      (contiguous host-array slices) together with its batch rows, and
      publish the device trees for the round loop's ``get(c)``.
    * ``("wb", c, dev)`` — write chunk ``c``'s updated bank back into
      the host array. The ``np.asarray`` D2H copy blocks until the
      chunk's (asynchronously dispatched) compute finishes — that is
      the only synchronization the pipeline needs.
    * ``("sync", event)`` — end-of-round barrier: when it fires, every
      prior write-back has landed and the host array is the post-round
      bank.

    FIFO ordering also guarantees a chunk's write-back precedes any
    later round's re-upload of the same rows. The round loop keeps two
    uploads in flight (``begin_round`` requests chunks 0 and 1;
    iteration ``c`` requests ``c+2``), so chunk ``c+1``'s H2D transfer
    overlaps chunk ``c``'s compute — the same double-buffer discipline
    :class:`RoundPrefetcher` applies to whole rounds.
    """

    def __init__(self, host_bank, chunk: int):
        self._bank = host_bank   # {name: {idx/val: np (Kp, nb, kb)}}
        self._chunk = chunk
        self._tasks: queue.Queue = queue.Queue()
        self._cv = threading.Condition()
        self._ready: dict = {}
        self._err: Optional[BaseException] = None
        self._batch = None
        self._n_chunks = 0
        self._requested: set = set()
        self._closed = False
        self._thread = threading.Thread(
            target=self._work, name="fl-bank-stream", daemon=True)
        self._thread.start()

    def begin_round(self, batch, n_chunks: int):
        """Arm the streamer with this round's host batch (chunked
        layout) and prefetch the first two chunks."""
        if self._err is not None:
            raise RuntimeError(
                "bank streamer thread failed") from self._err
        self._batch = batch
        self._n_chunks = n_chunks
        self._requested = set()
        with self._cv:
            self._ready.clear()
        self.request(0)
        self.request(1)

    def request(self, c: int):
        if 0 <= c < self._n_chunks and c not in self._requested:
            self._requested.add(c)
            self._tasks.put(("up", c))

    def get(self, c: int):
        """Device ``(bank_chunk, batch_chunk)`` for chunk ``c`` (blocks
        until its upload lands)."""
        with self._cv:
            while c not in self._ready:
                if self._err is not None:
                    raise RuntimeError(
                        "bank streamer thread failed") from self._err
                self._cv.wait(timeout=0.05)
            return self._ready.pop(c)

    def put_writeback(self, c: int, new_bank):
        self._tasks.put(("wb", c, new_bank))

    def finish_round(self):
        evt = threading.Event()
        self._tasks.put(("sync", evt))
        evt.wait()
        self._batch = None
        if self._err is not None:
            raise RuntimeError(
                "bank streamer thread failed") from self._err

    def close(self):
        if self._closed:
            return
        self._closed = True
        self._tasks.put(None)
        self._thread.join(timeout=10)

    def _work(self):
        while True:
            task = self._tasks.get()
            if task is None:
                return
            kind = task[0]
            try:
                if self._err is not None:
                    # after a failure only the barrier still fires (the
                    # round loop re-raises from get/finish_round)
                    if kind == "sync":
                        task[1].set()
                    continue
                if kind == "up":
                    c = task[1]
                    sl = slice(c * self._chunk, (c + 1) * self._chunk)
                    item = jax.device_put((
                        jax.tree.map(lambda a: a[sl], self._bank),
                        {k: v[c] for k, v in self._batch.items()}))
                    with self._cv:
                        self._ready[c] = item
                        self._cv.notify_all()
                elif kind == "wb":
                    c, dev = task[1], task[2]
                    sl = slice(c * self._chunk, (c + 1) * self._chunk)
                    host = jax.tree.map(np.asarray, dev)

                    def copy(dst, src):
                        dst[sl] = src
                    jax.tree.map(copy, self._bank, host)
                elif kind == "sync":
                    task[1].set()
            except BaseException as e:  # surfaced on the consumer side
                self._err = e
                with self._cv:
                    self._cv.notify_all()
                if kind == "sync":
                    task[1].set()
