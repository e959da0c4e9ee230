"""Canonical FL/LBGM knob container — the single source of truth.

``FLConfig`` is the one place the paper's Algorithm 1/3 knobs
(``delta_threshold``, ``k_frac`` via ``lbg_kw``, ``num_clients``,
``sample_frac``, ``tau``) and the engine's execution knobs (scheduler,
chunking, compressor pipeline) are defined. The arch-side view
``repro.configs.base.LBGMConfig`` is a thin shim over this class (its
shared defaults are read from ``FLConfig``'s fields and it converts via
``LBGMConfig.to_fl()`` / ``FLConfig.from_lbgm()``), so the two can no
longer drift.

``fused_kernels`` gates the engine's fused decision hot path (Pallas
projection/decision kernels + sparse scalar-round aggregation);
like every other field it is a plain JSON value (``None``/``true``/
``false``) and round-trips losslessly through ``to_dict``/``from_dict``
and any ``ExperimentSpec`` embedding it.

Every field is validated at construction (not at ``FLEngine.__init__``):
registry-keyed fields (``scheduler``, ``lbg_variant``, ``compressor``)
are checked against the live registries and the error lists the
registered names, so a typo fails immediately with the fix in the
message. The dataclass is frozen so an :class:`ExperimentSpec` embedding
it is immutable and safely shareable across sweep points.

This module stays import-light (no jax): registries are consulted
lazily, which also lets ``repro.configs`` import it without cycles.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple, Union

#: legacy spelling used by the arch-side LBGMConfig ("full" dense bank)
_LBG_VARIANT_ALIASES = {"full": "dense"}


@dataclass(frozen=True)
class FLConfig:
    num_clients: int = 100
    tau: int = 2                     # local SGD steps per round
    lr: float = 0.05
    batch_size: int = 32
    use_lbgm: bool = True
    delta_threshold: float = 0.2
    compressor: str = "none"         # registry key: none | topk | atomo | ...
    compressor_kw: Optional[dict] = None
    error_feedback: Optional[bool] = None   # default: on iff topk
    sample_frac: float = 1.0         # Algorithm 3 device sampling
    seed: int = 0
    scheduler: str = "vmap"          # registry key: vmap | chunked | ...
    chunk_size: int = 16             # max clients per lax.scan block
    mesh: Union[None, int, list] = None
    # ^ "sharded" scheduler: the 2-D (clients, model) device-mesh spec,
    #   resolved to a live named Mesh by ``launch.mesh.make_fl_mesh``.
    #   Three JSON-able spellings, all lossless through to_dict/from_dict:
    #     None     -> every local device on the client axis: (n_local, 1)
    #     int n    -> (n, 1) — the pre-2-D spelling; existing specs/CLIs
    #                 round-trip unchanged and run bit-for-bit identically
    #     [c, m]   -> c-way client-data-parallel x m-way model-axis
    #                 sharding of the LBG decision/banks (tuples are
    #                 normalized to lists so equality survives a JSON trip)
    model_sharding: str = "replicate"
    # ^ "sharded" scheduler: how each client's local-SGD forward/backward
    #   lays the MODEL out over the mesh's model axis.
    #     "replicate" (default) — every device holds the full params; only
    #                 the LBG bank / decision / aggregation rows shard over
    #                 ``model`` (bit-for-bit today's engine on every mesh).
    #     "auto"     — the model component's logical-axis tree (see
    #                 ``fed.experiment`` — the "lm" component carries its
    #                 arch's real axes) is resolved against the mesh via
    #                 ``train.sharding.param_pspec`` and the per-client
    #                 forward/backward runs tensor-parallel under GSPMD:
    #                 per-device params + activations scale as ~M/m, and
    #                 gradients arrive already laid out for the
    #                 model-sharded bank/decision path (fp32-tolerance
    #                 equal to "replicate", identical uplink accounting).
    #                 Requires scheduler="sharded", a metadata-carrying
    #                 model component, lbg_variant="topk-sharded" with
    #                 sparse aggregation, aggregator="mean", and
    #                 compressor="none" (validated at engine build).
    lbg_variant: str = "dense"       # registry key: dense | topk | null | ...
    lbg_kw: Optional[dict] = None    # e.g. {"k_frac": 0.1} for topk
    aggregator: str = "mean"         # registry key: mean | trimmed_mean |
    #   coordinate_median | geometric_median | ... "mean" (default) keeps
    #   the engine's streaming weighted-mean fold — bit-for-bit the
    #   pre-robustness round history on every scheduler; every other rule
    #   is Byzantine-robust and switches the schedulers into collect mode
    #   (per-client payload stacks, O(K·M) peak — see repro.fed.robust).
    aggregator_kw: Optional[dict] = None   # e.g. {"beta": 0.1} | {"iters": 8}
    attack: Optional[str] = None     # registry key: sign_flip | scaled |
    #   free_rider | gaussian | label_flip | ... None = no attack (default).
    attack_frac: float = 0.0         # fraction of clients made Byzantine
    #   (a fixed round(attack_frac*K) cohort, drawn deterministically from
    #   the seed — see repro.fed.attacks.select_byzantine)
    attack_kw: Optional[dict] = None       # e.g. {"sigma": 2.0} for gaussian
    dropout_frac: float = 0.0        # straggler fault injection: per round,
    #   each sampled client independently drops out with this probability
    #   (rides the participation-mask path; draws come from the dedicated
    #   fault stream, so the batch/mask rng stream is untouched)
    fused_kernels: Optional[bool] = None
    # ^ the LBGM decision hot path. None (default) = auto: sparse
    #   scalar-round aggregation wherever the LBG store supports it (any
    #   backend) + the Pallas decision kernels on TPU only (XLA
    #   fallback elsewhere). True forces the Pallas kernels on too
    #   (interpret mode off-TPU — for testing). False = the legacy dense
    #   path: per-client dense g_tilde scatter + 3-pass XLA decision,
    #   bit-for-bit identical to pre-knob round histories. Plain
    #   Optional[bool], so specs stay JSON-able and round-trip losslessly.
    codec: str = "none"              # registry key: none | delta_idx |
    #   int8 | fp8 — the uplink wire codec (repro.comm.wire). "none"
    #   (default) keeps the fp32 wire format and the pre-codec round
    #   history bit-for-bit; delta_idx compresses the sparse index stream
    #   losslessly; int8/fp8 stochastically quantize payload values and
    #   the scalar-round rho stream. Every codec feeds the real-byte
    #   ``wire_bytes`` ledger alongside the fp32-scalar counters.
    codec_kw: Optional[dict] = None  # e.g. {"stochastic": False} to pin
    #   nearest rounding for int8/fp8 (see repro.comm.wire)
    latency: str = "none"            # registry key: none | fixed | uniform |
    #   lognormal | straggler — the per-client rounds-of-delay model for
    #   scheduler="buffered" (repro.fed.latency). "none" (default) keeps
    #   every payload synchronous; any other model draws deterministic
    #   per-round delays from the dedicated fault stream, so the async
    #   replay is seed-exact and clean runs stay bit-for-bit untouched.
    latency_kw: Optional[dict] = None      # e.g. {"frac": 0.2, "delay": 4}
    #   for straggler, {"scale": 2.0} for lognormal; alpha sets the
    #   staleness discount 1/(1+s)^alpha every model carries;
    #   {"max_staleness": s} (any model) evicts-and-drops buffered
    #   payloads older than s rounds (counted in CommLedger.n_evicted)
    tiers: Union[None, list, dict] = None
    # ^ hierarchical aggregation tier map (repro.fed.hierarchy). None
    #   (default) = the flat single-server fold. Two JSON-able spellings:
    #     [e] or [e, r]            -> e edge servers (and optionally r
    #                                 regions) with contiguous balanced
    #                                 client assignment in client order
    #     {"levels": [e, r],       -> same levels, but "shuffle" derives a
    #      "assign": "shuffle"}       seed-dependent client permutation
    #   Clients fold into per-edge partial carries, edges into regions,
    #   regions into the global update. The global result is bit-for-bit
    #   the flat fold (the flat carry is kept alongside — see
    #   fed/hierarchy.py), and CommLedger attributes per-tier wire bytes:
    #   edge links carry the clients' sparse payloads, region/global
    #   links carry one dense partial-carry model each — the real comms
    #   saving at scale. Not supported with scheduler='sharded' (the
    #   wrapped carry pytree breaks the mesh partition specs).
    ckpt_every: int = 0              # checkpoint cadence in rounds; 0 = off.
    #   Every N completed rounds the engine atomically snapshots params +
    #   LBG banks + residuals + rng streams + buffered in-flight slots +
    #   the CommLedger to ckpt_path (repro.checkpoint.ckpt), and
    #   ``repro.fed.run --resume`` / ``FLEngine.run(resume=True)``
    #   continues a run from it bit-for-bit mid-stream.
    ckpt_path: Optional[str] = None  # .npz checkpoint target path

    # ---------------------------------------------------------- validation
    def __post_init__(self):
        def bad(msg):
            raise ValueError(f"FLConfig: {msg}")

        if self.num_clients < 1:
            bad(f"num_clients must be >= 1, got {self.num_clients}")
        if self.tau < 1:
            bad(f"tau must be >= 1, got {self.tau}")
        if self.batch_size < 1:
            bad(f"batch_size must be >= 1, got {self.batch_size}")
        if not 0.0 < self.sample_frac <= 1.0:
            bad(f"sample_frac must be in (0, 1], got {self.sample_frac}")
        if self.chunk_size < 1:
            bad(f"chunk_size must be >= 1, got {self.chunk_size}")
        # mesh stays a plain JSON value (None, int, or a 2-list) so the
        # config — and any ExperimentSpec embedding it — remains
        # JSON-serializable; the sharded scheduler resolves it to a live
        # 2-D (clients, model) Mesh at engine build. bools are ints in
        # Python, so reject them explicitly.
        def int_ge1(x):
            return isinstance(x, int) and not isinstance(x, bool) and x >= 1
        if self.mesh is not None:
            if isinstance(self.mesh, (list, tuple)):
                if len(self.mesh) != 2 or not all(int_ge1(d)
                                                  for d in self.mesh):
                    bad("mesh must be None, a client-device count >= 1, or "
                        "a [clients, model] pair of device counts >= 1 — "
                        f"got {self.mesh!r}")
                # canonicalize to a list: to_dict/JSON round-trips compare
                # equal no matter which sequence type the caller used
                object.__setattr__(self, "mesh", [int(d) for d in self.mesh])
            elif not int_ge1(self.mesh):
                bad("mesh must be None, a client-device count >= 1, or a "
                    f"[clients, model] pair — got {self.mesh!r}")
        if self.mesh_model_dim > 1 and self.scheduler in ("vmap", "chunked",
                                                          "buffered"):
            bad(f"mesh={self.mesh!r} asks for model-axis sharding but "
                f"scheduler={self.scheduler!r} is mesh-unaware; use "
                "scheduler='sharded' (the only built-in that runs the 2-D "
                "(clients, model) mesh)")
        if self.model_sharding not in ("replicate", "auto"):
            bad("model_sharding must be 'replicate' (every device holds "
                "the full params — today's engine) or 'auto' (tensor-"
                "parallel client compute from the model component's axis "
                f"metadata) — got {self.model_sharding!r}")
        if self.model_sharding == "auto" and self.scheduler != "sharded":
            bad(f"model_sharding='auto' shards the client forward/backward "
                "over the 2-D (clients, model) mesh, which only "
                f"scheduler='sharded' runs — got "
                f"scheduler={self.scheduler!r}")
        # identity check, not `in`: 0/1 compare == to False/True but would
        # silently miss the `is not False` gate in the engine's aggregator
        # selection — reject them with the fix in the message
        if not any(self.fused_kernels is v for v in (None, True, False)):
            bad("fused_kernels must be None (auto: Pallas on TPU, sparse "
                "aggregation everywhere), true, or false (legacy dense "
                f"path) — got {self.fused_kernels!r}; JSON/CLI specs must "
                "use the boolean literals, not 0/1")
        # robustness knobs: fractions in range, attack_frac only with an
        # attack named, kw dicts actually dicts
        if not 0.0 <= self.attack_frac <= 1.0:
            bad(f"attack_frac must be in [0, 1], got {self.attack_frac}")
        if not 0.0 <= self.dropout_frac < 1.0:
            bad(f"dropout_frac must be in [0, 1), got {self.dropout_frac}")
        if self.attack is None and self.attack_frac > 0:
            bad(f"attack_frac={self.attack_frac} but attack=None — name an "
                "attack (e.g. attack='sign_flip') or set attack_frac=0")
        for kw_name in ("aggregator_kw", "attack_kw", "codec_kw",
                        "latency_kw"):
            kw = getattr(self, kw_name)
            if kw is not None and not isinstance(kw, dict):
                bad(f"{kw_name} must be a dict or None, got {kw!r}")
        # buffered scheduler: latency models only make sense there, and
        # the scheduler itself folds sparse (idx, val) payload stacks
        # through the staleness buffer — it has no dense/legacy path
        if self.latency != "none" and self.scheduler != "buffered":
            bad(f"latency={self.latency!r} models rounds-of-delay for the "
                "buffered scheduler, but "
                f"scheduler={self.scheduler!r} folds every payload the "
                "round it is computed — use scheduler='buffered' or "
                "latency='none'")
        if self.scheduler == "buffered":
            if not self.use_lbgm or self.resolved_lbg_variant not in (
                    "topk", "topk-sharded"):
                bad("scheduler='buffered' buffers each client's sparse "
                    "(idx, val) payload between dispatch and delivery, "
                    "which needs the top-k LBG store — set use_lbgm=True "
                    "and lbg_variant='topk' (or 'topk-sharded'), got "
                    f"use_lbgm={self.use_lbgm} "
                    f"lbg_variant={self.lbg_variant!r}")
            if self.fused_kernels is False:
                bad("scheduler='buffered' requires the sparse aggregation "
                    "contract; fused_kernels=False selects the legacy "
                    "dense fold which cannot buffer payloads — leave "
                    "fused_kernels unset (auto) or True")
            if self.model_sharding != "replicate":
                bad("scheduler='buffered' runs the replicated chunked "
                    "layout; model_sharding="
                    f"{self.model_sharding!r} needs scheduler='sharded'")
        # topk-host keeps banks host-resident and streams them chunk-wise,
        # which only the chunked scheduler's fixed client-block layout
        # supports; dense residuals (error feedback) would reintroduce an
        # O(K, M) device tensor and defeat the point, so they are rejected
        if self.use_lbgm and self.resolved_lbg_variant == "topk-host":
            if self.scheduler != "chunked":
                bad("lbg_variant='topk-host' streams host-resident bank "
                    "chunks through the chunked client-block layout — set "
                    f"scheduler='chunked', got {self.scheduler!r}")
            ef_on = self.error_feedback is True or (
                self.error_feedback is None and self.compressor == "topk")
            if ef_on:
                bad("lbg_variant='topk-host' cannot run error feedback: "
                    "the dense (K, M) residual bank would live on device "
                    "and defeat out-of-core banks — set "
                    "error_feedback=False or compressor='none'")
            if self.fused_kernels is False:
                bad("lbg_variant='topk-host' requires the sparse "
                    "aggregation contract; fused_kernels=False selects "
                    "the legacy dense fold — leave fused_kernels unset "
                    "(auto) or True")
        # hierarchical tiers: validate the JSON spelling here (import-
        # light — the live TierMap is built at engine init)
        if self.tiers is not None:
            levels, assign = self.tiers, "contiguous"
            if isinstance(self.tiers, dict):
                unknown = set(self.tiers) - {"levels", "assign"}
                if unknown:
                    bad(f"tiers dict keys {sorted(unknown)} unknown; "
                        "valid keys: ['assign', 'levels']")
                levels = self.tiers.get("levels")
                assign = self.tiers.get("assign", "contiguous")
            if assign not in ("contiguous", "shuffle"):
                bad("tiers assign must be 'contiguous' or 'shuffle', "
                    f"got {assign!r}")
            if (not isinstance(levels, (list, tuple)) or
                    not 1 <= len(levels) <= 2 or
                    not all(int_ge1(n) for n in levels)):
                bad("tiers levels must be [n_edges] or "
                    "[n_edges, n_regions] with ints >= 1, got "
                    f"{levels!r}")
            levels = [int(n) for n in levels]
            if levels[0] > self.num_clients:
                bad(f"tiers asks for {levels[0]} edges but only "
                    f"{self.num_clients} clients exist")
            if len(levels) == 2 and levels[1] > levels[0]:
                bad(f"tiers levels must descend edge -> region, got "
                    f"{levels!r}")
            # canonicalize sequences to lists for JSON-trip equality
            if isinstance(self.tiers, dict):
                object.__setattr__(
                    self, "tiers", {"levels": levels, "assign": assign})
            else:
                object.__setattr__(self, "tiers", levels)
            if self.scheduler == "sharded":
                bad("tiers are not supported with scheduler='sharded': "
                    "the hierarchical carry pytree has no mesh partition "
                    "spec — use vmap/chunked/buffered")
        if self.ckpt_every < 0:
            bad(f"ckpt_every must be >= 0, got {self.ckpt_every}")
        if self.ckpt_every > 0 and not self.ckpt_path:
            bad(f"ckpt_every={self.ckpt_every} needs a ckpt_path to "
                "write to")
        # registry-keyed fields: fail now, with the registered names in the
        # message, instead of deep inside the engine build
        from repro.fed import registry as reg
        if self.scheduler not in reg.SCHEDULERS:
            bad(f"unknown scheduler {self.scheduler!r}; registered "
                f"schedulers: {reg.SCHEDULERS.names()}")
        if self.use_lbgm and self.resolved_lbg_variant not in reg.LBG_STORES:
            bad(f"unknown lbg_variant {self.lbg_variant!r}; registered "
                f"lbg_stores: {reg.LBG_STORES.names()}")
        if self.compressor not in reg.COMPRESSORS:
            bad(f"unknown compressor {self.compressor!r}; registered "
                f"compressors: {reg.COMPRESSORS.names()}")
        if self.aggregator not in reg.AGGREGATORS:
            bad(f"unknown aggregator {self.aggregator!r}; registered "
                f"aggregators: {reg.AGGREGATORS.names()}")
        if self.attack is not None and self.attack not in reg.ATTACKS:
            bad(f"unknown attack {self.attack!r}; registered "
                f"attacks: {reg.ATTACKS.names()}")
        if self.codec not in reg.CODECS:
            bad(f"unknown codec {self.codec!r}; registered "
                f"codecs: {reg.CODECS.names()}")
        if self.latency not in reg.LATENCIES:
            bad(f"unknown latency {self.latency!r}; registered "
                f"latency models: {reg.LATENCIES.names()}")
        # *_kw keys checked against the registered component's signature
        # (or its explicit kw= spec) — a typo'd key fails here with the
        # valid names, not as a TypeError deep inside the engine build.
        # valid_kw returns None for unintrospectable factories: skip.
        for field, kw_name, registry in (
                ("aggregator", "aggregator_kw", reg.AGGREGATORS),
                ("attack", "attack_kw", reg.ATTACKS),
                ("codec", "codec_kw", reg.CODECS),
                ("latency", "latency_kw", reg.LATENCIES)):
            comp, kw = getattr(self, field), getattr(self, kw_name)
            if comp is None or not kw:
                continue
            valid = registry.valid_kw(comp)
            if valid is None:
                continue
            unknown = sorted(set(kw) - valid)
            if unknown:
                bad(f"{kw_name} keys {unknown} are not accepted by "
                    f"{field}={comp!r}; valid keys: {sorted(valid)}")

    # ------------------------------------------------------------- views
    @property
    def resolved_lbg_variant(self) -> str:
        return _LBG_VARIANT_ALIASES.get(self.lbg_variant, self.lbg_variant)

    @property
    def mesh_shape(self) -> Optional[Tuple[int, int]]:
        """The (clients, model) mesh shape, or None for "every local
        device on the client axis" (resolved at engine build, where the
        device count is known). An int spec is exactly ``(n, 1)``."""
        if self.mesh is None:
            return None
        if isinstance(self.mesh, int):
            return (self.mesh, 1)
        return (self.mesh[0], self.mesh[1])

    @property
    def mesh_model_dim(self) -> int:
        """Model-axis extent of the mesh (1 unless a 2-D spec asks for
        model sharding) — importable without jax, so stores/validators can
        branch on it before any device exists."""
        shape = self.mesh_shape
        return 1 if shape is None else shape[1]

    def replace(self, **overrides) -> "FLConfig":
        return dataclasses.replace(self, **overrides)

    # ------------------------------------------------------ serialization
    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "FLConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(
                f"FLConfig: unknown fields {sorted(unknown)}; "
                f"known fields: {sorted(known)}")
        return cls(**d)

    # ------------------------------------------------- arch-config bridge
    @classmethod
    def from_lbgm(cls, lbgm, **overrides) -> "FLConfig":
        """Build from an arch-side ``configs.base.LBGMConfig`` view."""
        kw = dict(
            use_lbgm=lbgm.enabled,
            lbg_variant=lbgm.variant,
            delta_threshold=lbgm.delta_threshold,
            num_clients=lbgm.num_clients,
            tau=lbgm.local_steps,
            sample_frac=lbgm.sample_frac,
        )
        if _LBG_VARIANT_ALIASES.get(lbgm.variant, lbgm.variant) == "topk":
            kw["lbg_kw"] = {"k_frac": lbgm.k_frac}
        kw.update(overrides)
        return cls(**kw)
