"""Plain reference of the federated round, for the comparison that decides
``correct``.

It follows the round's definition, client by client and leaf by leaf, in
plain ``jax.numpy``: ``tau`` local SGD steps per client on batches drawn
from the same seeded stream the engine is handed, the Algorithm-1 decision
of the configured LBG store (none, dense, or block-wise top-k), the wire
codec (none, or int8 with per-row power-of-two scales and stochastic
rounding), the weighted mean fold and the server step. Each round also
counts its scalar uploads and the bytes its uplinks put on the wire, by
the wire format's byte model: 4 bytes a float32 value or index, and for
int8 one byte a value, a 4-byte scale per block row, indices sent sorted
as varint deltas (1, 2 or 3 bytes) and one byte for a scalar round's
rho. No Pallas, no scan, no vmap over clients, no program code, and no
program-made array: the weights come from the model file's ``init`` and
the data from the traffic generators, both from the run's seed.

``dtype=float32`` runs under ``jax.default_matmul_precision("highest")``;
``dtype=bfloat16`` is the control (weights, activations and local steps
in bfloat16, the decision and fold in float32). ``batch_frac=0.5`` is the
half-batch fault: each step sees the first half of its rows.
"""
from __future__ import annotations

import contextlib
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-20
BLOCK = 65536


def block_layout(size: int, k_frac: float):
    """(nb, block, kb) of the top-k store for a leaf of ``size``: block-wise
    top-kb over contiguous 65,536-entry blocks, nb rounded up to 16."""
    block = min(size, BLOCK)
    nb = -(-size // block)
    if nb > 1:
        nb = -(-nb // 16) * 16
    k = max(1, int(size * k_frac))
    kb = max(1, min(block, k // nb if nb > 1 else k))
    return nb, block, kb


def codec_seeds(fl_seed: int):
    """The per-round, per-client stochastic-rounding seed stream."""
    return np.random.RandomState((fl_seed + 0xC0DEC) * 16807 % (2 ** 31))


def _pow2_scale(m, qmax):
    e = jnp.ceil(jnp.log2(jnp.maximum(m, 1e-38) / qmax)).astype(jnp.int32)
    return jnp.where(m > 0, jnp.ldexp(jnp.ones_like(m), e), 1.0)


def _int8(val, key):
    """Stochastically rounded int8 grid values, dequantized, per row."""
    scale = _pow2_scale(jnp.max(jnp.abs(val), -1, keepdims=True), 127.0)
    f = val / scale
    lo = jnp.floor(f)
    q = lo + (jax.random.uniform(key, f.shape) < (f - lo))
    return jnp.clip(q, -127.0, 127.0) * scale


def _e4m3(x):
    return jnp.clip(x, -448.0, 448.0).astype(jnp.float8_e4m3fn).astype(
        jnp.float32)


class Round:
    """The reference's state and compiled pieces for one cell."""

    def __init__(self, model, cfg: dict, fl: dict, params: Dict,
                 data: Dict[str, np.ndarray], parts: List[np.ndarray],
                 fl_seed: int, dtype=jnp.float32, batch_frac: float = 1.0):
        if fl.get("sample_frac", 1.0) != 1.0:
            raise ValueError("the reference samples every client each round")
        self.fl, self.K = fl, len(parts)
        self.tau, self.b, self.lr = fl["tau"], fl["batch_size"], fl["lr"]
        self.store = (fl.get("lbg_variant", "dense")
                      if fl.get("use_lbgm", True) else "null")
        if self.store not in ("null", "dense", "topk"):
            raise ValueError(f"no reference for lbg_variant {self.store!r}")
        self.codec = fl.get("codec", "none")
        if self.codec not in ("none", "int8"):
            raise ValueError(f"no reference for codec {self.codec!r}")
        if self.codec == "int8" and (fl.get("codec_kw") or {}).get(
                "stochastic") is False:
            raise ValueError("the reference rounds int8 stochastically")
        if self.codec == "int8" and self.store != "topk":
            raise ValueError("the reference quantizes top-k payloads only")
        self.delta = fl.get("delta_threshold", 0.2)
        self.k_frac = (fl.get("lbg_kw") or {}).get("k_frac", 0.1)
        self.dtype = dtype
        self.prec = (jax.default_matmul_precision("highest")
                     if dtype == jnp.float32 else contextlib.nullcontext())
        self.params = {k: v.astype(dtype) for k, v in params.items()}
        self.data, self.parts = data, parts
        sizes = np.array([len(p) for p in parts], np.float64)
        self.w = sizes / sizes.sum()
        self.rng = np.random.RandomState(fl_seed + 1)
        self.seeds = codec_seeds(fl_seed)
        self.scalars: List[int] = []
        self.wires: List[float] = []
        self.dense_bytes = 4.0 * sum(int(v.size) for v in params.values())
        self.nb = max(1, int(self.b * batch_frac))
        self.layout = {n: block_layout(int(v.size), self.k_frac)
                       for n, v in params.items()}
        if self.store == "dense":
            self.bank = [None] * self.K
        elif self.store == "topk":
            self.bank = [{n: (jnp.zeros((nb, kb), jnp.int32),
                              jnp.zeros((nb, kb), jnp.float32))
                          for n, (nb, _, kb) in self.layout.items()}
                         for _ in range(self.K)]
        loss = lambda p, bt: model.batch_loss(p, bt, cfg)
        self._client = jax.jit(self._client_fn(loss))
        self._topk = jax.jit(self._topk_fn)
        self._dense = jax.jit(self._dense_fn)

    # ------------------------------------------------------------ pieces
    def _client_fn(self, loss):
        lr, tau = self.lr, self.tau

        def client(params, batches):
            asg = {k: jnp.zeros(v.shape, jnp.float32)
                   for k, v in params.items()}
            ls = []
            p = params
            for t in range(tau):
                bt = {k: v[t] for k, v in batches.items()}
                l, g = jax.value_and_grad(loss)(p, bt)
                p = {k: p[k] - lr * g[k].astype(p[k].dtype) for k in p}
                asg = {k: asg[k] + g[k].astype(jnp.float32) for k in asg}
                ls.append(l.astype(jnp.float32))
            return asg, sum(ls) / tau
        return client

    def _dense_fn(self, g, l):
        """Algorithm 1 over a dense LBG: (g_tilde, new_lbg, scalar)."""
        gl = sum(jnp.vdot(g[k], l[k]) for k in g)
        gg = sum(jnp.vdot(g[k], g[k]) for k in g)
        ll = sum(jnp.vdot(l[k], l[k]) for k in g)
        sin2 = jnp.where(ll > EPS, 1.0 - gl * gl / jnp.maximum(gg * ll, EPS),
                         1.0)
        rho = gl / jnp.maximum(ll, EPS)
        scalar = (sin2 <= self.delta) & (sin2 < 1.0)
        gt = {k: jnp.where(scalar, rho * l[k], g[k]) for k in g}
        new = {k: jnp.where(scalar, l[k], g[k]) for k in g}
        return gt, new, scalar

    def _topk_fn(self, g, bank, seed):
        """Algorithm 1 over the block-wise top-k LBG, then the codec:
        (payload {name: (idx, val)}, gscale, new bank, scalar, wire
        bytes)."""
        blocks = {}
        gl = gg = ll = jnp.zeros((), jnp.float32)
        for n, (nb, block, kb) in self.layout.items():
            flat = g[n].reshape(-1)
            bl = jnp.pad(flat, (0, nb * block - flat.size)).reshape(nb, block)
            blocks[n] = bl
            idx, val = bank[n]
            gv = jnp.take_along_axis(bl, idx, axis=1)
            gl += jnp.vdot(gv, val)
            ll += jnp.vdot(val, val)
            gg += jnp.vdot(flat, flat)
        sin2 = jnp.where(ll > EPS, 1.0 - gl * gl / jnp.maximum(gg * ll, EPS),
                         1.0)
        rho = gl / jnp.maximum(ll, EPS)
        scalar = (sin2 <= self.delta) & (sin2 < 1.0)
        key = jax.random.PRNGKey(seed)
        send = {}
        full = jnp.zeros((), jnp.float32)
        for i, n in enumerate(sorted(self.layout)):
            kb = self.layout[n][2]
            _, ti = jax.lax.top_k(jnp.abs(blocks[n]), kb)
            tv = jnp.take_along_axis(blocks[n], ti, axis=1)
            idx = jnp.where(scalar, bank[n][0], ti.astype(jnp.int32))
            val = jnp.where(scalar, bank[n][1], tv)
            if self.codec == "int8":
                val = _int8(val, jax.random.fold_in(key, i))
                full += _varint_delta_bytes(idx) + (kb + 4.0) * idx.shape[0]
            else:
                full += 8.0 * idx.size
            send[n] = (idx, val)
        if self.codec == "int8":
            gscale = jnp.where(scalar, _e4m3(rho), 1.0)
            wire = jnp.where(scalar, 1.0, full)
        else:
            gscale = jnp.where(scalar, rho, 1.0)
            wire = jnp.where(scalar, 4.0, full)
        return send, gscale, send, scalar, wire

    # ------------------------------------------------------------- round
    def _batches(self, k, idx):
        rows = self.parts[k][idx[:, :self.nb]]
        return {name: jnp.asarray(v[rows]) for name, v in self.data.items()}

    def step(self) -> float:
        """One round; returns its weighted mean client loss and appends
        its count of scalar uploads to ``self.scalars``."""
        draws = [self.rng.randint(0, len(p), size=(self.tau, self.b))
                 for p in self.parts]
        seeds = (self.seeds.randint(0, 2 ** 31 - 1, size=self.K)
                 .astype(np.uint32) if self.codec == "int8" else None)
        with self.prec:
            acc = ({n: jnp.zeros((nb, block), jnp.float32)
                    for n, (nb, block, _) in self.layout.items()}
                   if self.store == "topk" else
                   {k: jnp.zeros(v.shape, jnp.float32)
                    for k, v in self.params.items()})
            loss, n_scalar, wire = 0.0, 0, 0.0
            for k in range(self.K):
                asg, lk = self._client(self.params,
                                       self._batches(k, draws[k]))
                loss += self.w[k] * float(lk)
                if self.store == "null":
                    acc = _axpy(acc, self.w[k], asg)
                    wire += self.dense_bytes
                elif self.store == "dense":
                    lbg = self.bank[k] or {n: jnp.zeros_like(v)
                                           for n, v in asg.items()}
                    gt, self.bank[k], scalar = self._dense(asg, lbg)
                    n_scalar += bool(scalar)
                    wire += 4.0 if bool(scalar) else self.dense_bytes
                    acc = _axpy(acc, self.w[k], gt)
                else:
                    send, gscale, self.bank[k], scalar, wk = self._topk(
                        asg, self.bank[k],
                        jnp.uint32(0 if seeds is None else seeds[k]))
                    n_scalar += bool(scalar)
                    wire += float(wk)
                    acc = _scatter(acc, self.w[k] * gscale, send)
                del asg
            if self.store == "topk":
                acc = {n: a.reshape(-1)[:self.params[n].size]
                       .reshape(self.params[n].shape)
                       for n, a in acc.items()}
            self.params = _server(self.params, acc, self.lr)
        self.scalars.append(n_scalar)
        self.wires.append(wire)
        return loss


def _varint_delta_bytes(idx):
    """Bytes of a (rows, kb) index block sent per row sorted, as the first
    index and then the deltas, each a varint of 7 bits a byte."""
    s = jnp.sort(idx, axis=-1)
    d = jnp.diff(s, axis=-1, prepend=jnp.zeros_like(s[:, :1]))
    return jnp.sum(1.0 + (d >= 2 ** 7) + (d >= 2 ** 14))


@jax.jit
def _axpy(acc, w, g):
    return {k: acc[k] + w * g[k] for k in acc}


@jax.jit
def _scatter(acc, coeff, send):
    out = {}
    for n, a in acc.items():
        idx, val = send[n]
        rows = jnp.arange(a.shape[0])[:, None]
        out[n] = a.at[rows, idx].add(coeff * val)
    return out


@jax.jit
def _server(params, agg, lr):
    return {k: p - lr * agg[k].astype(p.dtype) for k, p in params.items()}


@jax.jit
def leaf_norms(a, b):
    """Per-leaf ||a - b|| in float32."""
    return {k: jnp.linalg.norm((a[k].astype(jnp.float32)
                                - b[k].astype(jnp.float32)).reshape(-1))
            for k in a}


def run(model, cfg, fl, params0, data, parts, fl_seed, rounds: int = 3,
        dtype=jnp.float32, batch_frac: float = 1.0) -> dict:
    """Readings of the reference over its first ``rounds`` rounds: each
    round's loss, count of scalar uploads and wire bytes, and per-leaf
    norms of the change after round 1 and after the last round."""
    r = Round(model, cfg, fl, params0, data, parts, fl_seed, dtype=dtype,
              batch_frac=batch_frac)
    start = r.params
    losses, d1 = [], None
    for i in range(rounds):
        losses.append(r.step())
        if i == 0:
            d1 = _host(leaf_norms(r.params, start))
    return {"loss": losses, "scalars": r.scalars, "wire": r.wires, "d1": d1,
            "dn": _host(leaf_norms(r.params, start))}


def _host(tree):
    return {k: float(v) for k, v in tree.items()}
