"""Gradient compressors: gs-SGD (the paper) and every baseline it compares to.

All compressors share one contract so the training loop, the convergence
benchmarks and the dry-run lowering treat them uniformly:

    state              = compressor.init(d)
    upd_sum, state, nfo = compressor.step(state, g_local, axis=..., nworkers=P)

``g_local`` is this worker's (error-corrected input to) flat local gradient;
``upd_sum`` is the dense SUM over workers of the applied update (caller
divides by P). ``axis`` names the data-parallel mesh axes of the enclosing
``jax.shard_map`` — or of a ``jax.vmap(..., axis_name=...)``, which is how the
CPU convergence benchmarks simulate P workers with bit-identical collective
semantics.

Compressors:
  DenseAllReduce   — vanilla synchronous S-SGD (no compression).
  TopKCompressor   — local Top-k, PS-style aggregation (centralized baseline).
  GTopK            — gTop-k [23]: tree-merged global Top-k (decentralized).
  SketchedSGD      — Sketched-SGD [22]: Count-Sketch + parameter-server
                     aggregation, emulated with all_gather => O(logd * P) comm.
  GsSGD            — THE PAPER: Count-Sketch + decentralized all-reduce of
                     sketches (psum or faithful Alg.1 ppermute tree) +
                     HEAVYMIX + exact second round => O(logd * logP) comm.

Every step returns a ``CommStats`` (static python numbers derived from shapes)
consumed by the paper-figure benchmarks and the roofline model.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from repro.core import allreduce as ar
from repro.core import count_sketch as cs
from repro.core import error_feedback as ef
from repro.core import heavymix as hm
from repro.kernels import ops as kops
from repro.obs import trace as obtrace

Array = jax.Array
AxisNames = str | Sequence[str]

_F32 = 4  # wire bytes per float32
_I32 = 4


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class CommStats:
    """Per-worker communication volume of one aggregation step (static
    python numbers — rides through jit/vmap as a static pytree leaf)."""

    bytes_out: float  # payload bytes this worker injects into the network
    rounds: int       # latency term: sequential communication rounds
    label: str = ""

    def time(self, alpha: float, beta: float) -> float:
        """Paper Eq.1 cost model: rounds*alpha + bytes*beta."""
        return self.rounds * alpha + self.bytes_out * beta


def _ring_allreduce_bytes(nbytes: float, p: int) -> float:
    """Bandwidth-optimal all-reduce: 2*(P-1)/P of the payload per worker."""
    return 2.0 * (p - 1) / p * nbytes


def _scatter(d: int, idx: Array, vals: Array) -> Array:
    return jnp.zeros((d,), jnp.float32).at[idx].set(vals)


# ---------------------------------------------------------------------------


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class DenseAllReduce:
    """No compression — the classic synchronous data-parallel baseline."""

    name: str = "dense"

    def init(self, d: int) -> Any:
        return ()

    def comm_stats(self, d: int, nworkers: int) -> CommStats:
        return CommStats(_ring_allreduce_bytes(d * _F32, nworkers),
                         rounds=2 * (nworkers - 1), label=self.name)

    def step(self, state, g: Array, *, axis: AxisNames, nworkers: int,
             key: Array | None = None):
        with obtrace.phase("comm", "allreduce"):
            upd = jax.lax.psum(g.astype(jnp.float32), axis)
        stats = self.comm_stats(g.size, nworkers)
        return upd, state, stats


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class TopKCompressor:
    """Local Top-k with centralized (PS-style) aggregation + error feedback.

    The PS inbox is emulated with a psum of the k-sparse local selections —
    identical math, and the comm volume is modeled as the PS up/down link
    (k values + k indices per worker, O(k*P) at the server hotspot).
    """

    k: int
    name: str = "topk"

    def init(self, d: int) -> Array:
        return ef.init(d)

    def comm_stats(self, d: int, nworkers: int) -> CommStats:
        return CommStats(2 * self.k * (_F32 + _I32), rounds=2,
                         label=self.name)

    def step(self, acc: Array, g: Array, *, axis: AxisNames, nworkers: int,
             key: Array | None = None):
        u = ef.add(acc, g)
        d = u.shape[0]
        _, idx = jax.lax.top_k(jnp.abs(u), self.k)
        local = _scatter(d, idx, u[idx])
        upd = jax.lax.psum(local, axis)
        acc = ef.residual_dense(u, local)
        return upd, acc, self.comm_stats(d, nworkers)


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class GTopK:
    """gTop-k [23]: decentralized tree merge keeping only k survivors per hop.

    Each reduce round ships 2k numbers (values + coordinates — Top-k methods
    must send coordinates, doubling the payload; the paper contrasts this
    with sketches, which need none). The merged set is re-sparsified to k
    after every hop, which is exactly the convergence-hurting approximation
    gs-SGD removes.
    """

    k: int
    name: str = "gtopk"

    def init(self, d: int) -> Array:
        return ef.init(d)

    def _sparsify(self, x: Array) -> Array:
        _, idx = jax.lax.top_k(jnp.abs(x), self.k)
        return _scatter(x.shape[0], idx, x[idx])

    def comm_stats(self, d: int, nworkers: int) -> CommStats:
        rounds = ar.tree_allreduce_rounds(nworkers)
        return CommStats(rounds * self.k * (_F32 + _I32), rounds=rounds,
                         label=self.name)

    def step(self, acc: Array, g: Array, *, axis: AxisNames, nworkers: int,
             key: Array | None = None):
        if not isinstance(axis, str):
            if len(axis) != 1:
                raise ValueError("gTop-k tree needs a single flat DP axis")
            axis = axis[0]
        u = ef.add(acc, g)
        s = self._sparsify(u)
        sched = ar.reduce_schedule(nworkers)
        for pairs in sched:  # recursive halving; merged set re-sparsified
            received, mask = ar.masked_permute(s, axis, pairs, nworkers)
            merged = s + jnp.where(mask, received, jnp.zeros_like(received))
            s = jnp.where(mask, self._sparsify(merged), s)
        for pairs in reversed(sched):  # broadcast the survivors back
            back = [(dst, src) for (src, dst) in pairs]
            received, mask = ar.masked_permute(s, axis, back, nworkers)
            s = jnp.where(mask, received, s)
        # EF: zero the globally surviving coordinates in u.
        _, idx = jax.lax.top_k(jnp.abs(s), self.k)
        acc = ef.residual_global(u, idx)
        return s, acc, self.comm_stats(u.shape[0], nworkers)


# ---------------------------------------------------------------------------
# Sketch-based compressors (Sketched-SGD baseline + gs-SGD, the paper).
# ---------------------------------------------------------------------------


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class _SketchBased:
    k: int = 1024
    sketch: cs.SketchConfig = cs.SketchConfig()
    faithful_heavymix: bool = False
    use_pallas: bool = False  # Pallas encode/decode (interpret on CPU)
    encoder: str = "exact"    # 'exact' (multiply-shift) | 'ts' (O(d*R)
    #   TPU-native shifted-window variant — beyond-paper, see ts_sketch.py)
    name: str = "sketch-base"

    def init(self, d: int) -> Array:
        return ef.init(d)

    def _ts_cfg(self, d: int):
        from repro.core.ts_sketch import TSketchConfig
        return TSketchConfig(d=d, rows=self.sketch.rows,
                             width=self.sketch.width, seed=self.sketch.seed)

    def _encode(self, u: Array) -> Array:
        if self.encoder == "ts":
            from repro.core import ts_sketch as ts
            return ts.encode(self._ts_cfg(u.shape[0]), u)
        return kops.encode(self.sketch, u, use_pallas=self.use_pallas or None)

    def _recover(self, sketch_sum: Array, u: Array, d: int, *,
                 axis: AxisNames, key: Array | None,
                 include: Array | None = None, scale: Array | None = None):
        """HEAVYMIX + exact second round. Returns (upd_sum, idx).

        include/scale: straggler-drop support — this worker's exact values
        join the second round only if ``include``; the sum is rescaled by
        ``scale`` = P/live (unbiased estimate of the full-P sum).
        """
        est = None
        if self.encoder == "ts":
            from repro.core import ts_sketch as ts
            with jax.named_scope("recover/decode"):
                est = ts.decode(self._ts_cfg(d), sketch_sum, d)
        idx, _ = hm.heavymix(self.sketch, sketch_sum, self.k, d, key=key,
                             faithful=self.faithful_heavymix, estimates=est,
                             use_pallas=self.use_pallas or None)
        # Second round (Alg.2 line 4): exact values of Top_k, k floats.
        with jax.named_scope("recover/second_round"):
            vals = u[idx] if include is None else u[idx] * include
            vals = jax.lax.psum(vals, axis)
            if scale is not None:
                vals = vals * scale
            return _scatter(d, idx, vals), idx


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class SketchedSGD(_SketchBased):
    """Sketched-SGD [22]: PS aggregation of sketches — O(log d * P) comm.

    TPU pods have no parameter server; the PS inbox (every worker's sketch
    arriving at one place) is reproduced with all_gather so the per-worker
    traffic keeps the O(S * P) scaling of the centralized original.
    """

    name: str = "sketched-sgd"

    def comm_stats(self, d: int, nworkers: int) -> CommStats:
        sk_bytes = self.sketch.size * _F32
        return CommStats(sk_bytes * nworkers + self.k * _F32,
                         rounds=nworkers, label=self.name)

    def step(self, acc: Array, g: Array, *, axis: AxisNames, nworkers: int,
             key: Array | None = None):
        with obtrace.phase("encode"):
            u = ef.add(acc, g)
            sk = self._encode(u)
        d = u.shape[0]
        with obtrace.phase("comm", "allgather"):
            gathered = jax.lax.all_gather(sk, axis)  # (P, R, W): PS inbox
            sk_sum = jnp.sum(gathered.reshape(-1, *sk.shape), axis=0)
        with obtrace.phase("recover"):
            upd, idx = self._recover(sk_sum, u, d, axis=axis, key=key)
            with jax.named_scope("recover/second_round"):
                acc = ef.residual_global(u, idx)
        return upd, acc, self.comm_stats(d, nworkers)


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class GsSGD(_SketchBased):
    """THE PAPER: global-sketching SGD.

    Sketch locally, all-reduce the (linear, mergeable) sketches
    decentralized, recover Top-k via HEAVYMIX from the identical summed
    sketch on every worker, fetch exact values with a k-float second round.
    Comm: O(log d) payload * O(log P) rounds (tree) — no coordinates ever
    cross the wire.

    allreduce_mode: 'psum' (TPU-native, production) | 'tree' (faithful Alg.1).
    wire_dtype:     sketch dtype on the wire; bf16 halves collective bytes
                    (beyond-paper knob, validated for estimate error in tests).
    """

    allreduce_mode: str = "psum"
    wire_dtype: Any = jnp.float32
    name: str = "gs-sgd"

    # The step is exposed as three pipeline stages so the bucket scheduler
    # in ``core/gs_sgd.py`` can interleave bucket i's all-reduce with bucket
    # i+1's encode. ``step`` composes them — single source of the numerics.

    def stage_encode(self, acc: Array, g: Array) -> tuple[Array, Array]:
        """Stage 1 (compute): EF add + local Count-Sketch encode."""
        u = ef.add(acc, g)
        return u, self._encode(u).astype(self.wire_dtype)

    # Fused-encode support (DESIGN.md §7): stage 1 split into per-fragment
    # partial encodes so the interleaved scheduler can sketch each VJP chunk
    # the moment it emits, instead of waiting for the bucket's full range.
    # Correctness rests on two linearities: EF add is elementwise (slicing
    # commutes bit-exactly), and S(a + b) = S(a) + S(b) with offset hashing
    # making partial sketches over a disjoint tiling sum to the full encode.

    @property
    def can_fuse(self) -> bool:
        """Fragment-wise encode available? The 'ts' encoder's shifted-window
        hashing has no offset form — only the exact multiply-shift encoder
        fuses."""
        return self.encoder == "exact"

    def stage_encode_partial(self, acc_piece: Array, g_piece: Array,
                             offset: int) -> tuple[Array, Array]:
        """Stage 1, one fragment: EF add + partial encode of the bucket
        slice [offset, offset + len(g_piece)). Returns (u_piece, partial
        f32 sketch); ``stage_encode_merge`` assembles the bucket."""
        with jax.named_scope("encode"):
            u_piece = ef.add(acc_piece, g_piece)
            sk = kops.encode(self.sketch, u_piece, offset=int(offset),
                             use_pallas=self.use_pallas or None)
        return u_piece, sk

    def stage_encode_merge(self, pieces) -> tuple[Array, Array]:
        """Assemble fragments into the bucket's (u, wire sketch).

        ``pieces``: [(offset, u_piece, partial_sketch)] covering the bucket
        contiguously (any order). Partials are summed in f32 in ascending
        offset order, then cast to ``wire_dtype`` — matching
        ``stage_encode``'s encode-then-cast, so fusing never changes what
        crosses the wire beyond fp summation grouping.
        """
        pieces = sorted(pieces, key=lambda p: p[0])
        off = 0
        for o, u_piece, _ in pieces:
            if int(o) != off:
                raise ValueError(
                    "fused encode fragments do not tile the bucket: "
                    f"expected offset {off}, got {int(o)}")
            off += u_piece.shape[0]
        u = jnp.concatenate([p[1] for p in pieces])
        sk = pieces[0][2]
        for _, _, part in pieces[1:]:
            sk = sk + part
        return u, sk.astype(self.wire_dtype)

    def stage_reduce(self, sk: Array, *, axis: AxisNames, nworkers: int,
                     include: Array | None = None):
        """Stage 2 (communication): merge the linear sketches over workers.

        include: () bool — straggler drop-mask (True = my sketch counts).
        An excluded worker's sketch contributes zero (linearity makes the
        merged sketch exact for the live subset); returns the P/live
        rescale for the unbiased full-P estimate (None without a mask).
        """
        scale = None
        if include is not None:
            include = include.astype(jnp.float32)
            live = jax.lax.psum(include, axis)
            scale = nworkers / jnp.maximum(live, 1.0)
            sk = sk * include.astype(sk.dtype)
        sk_sum = ar.allreduce(sk, axis, nworkers,
                              mode=self.allreduce_mode).astype(jnp.float32)
        return sk_sum, scale

    def stage_recover(self, u: Array, sk_sum: Array, scale, *,
                      axis: AxisNames, nworkers: int,
                      key: Array | None = None,
                      include: Array | None = None):
        """Stage 3: HEAVYMIX + exact second round + EF residual update."""
        d = u.shape[0]
        inc = include.astype(jnp.float32) if include is not None else None
        upd, idx = self._recover(sk_sum, u, d, axis=axis, key=key,
                                 include=inc, scale=scale)
        with jax.named_scope("recover/second_round"):
            if include is None:
                acc = ef.residual_global(u, idx)
            else:  # dropped workers keep their entire update for next step
                acc = jnp.where(inc > 0, ef.residual_global(u, idx), u)
        return upd, acc, self.comm_stats(d, nworkers)

    def comm_stats(self, d: int, nworkers: int) -> CommStats:
        """Static wire model of one step (also used by the benchmarks)."""
        wire = jnp.dtype(self.wire_dtype).itemsize
        if self.allreduce_mode == "tree":
            rounds = ar.tree_allreduce_rounds(nworkers)
            sk_bytes = rounds * self.sketch.size * wire
        else:
            rounds = 2 * (nworkers - 1)
            sk_bytes = _ring_allreduce_bytes(self.sketch.size * wire, nworkers)
        return CommStats(sk_bytes + self.k * _F32, rounds=rounds + 2,
                         label=self.name)

    def step(self, acc: Array, g: Array, *, axis: AxisNames, nworkers: int,
             key: Array | None = None, include: Array | None = None):
        with obtrace.phase("encode") as sp:
            u, sk = self.stage_encode(acc, g)
            sp.sync(sk)
        with obtrace.phase("comm", "allreduce") as sp:
            sk_sum, scale = self.stage_reduce(sk, axis=axis,
                                              nworkers=nworkers,
                                              include=include)
            sp.sync(sk_sum)
        with obtrace.phase("recover") as sp:
            out = self.stage_recover(u, sk_sum, scale, axis=axis,
                                     nworkers=nworkers, key=key,
                                     include=include)
            sp.sync(out[0])
        return out


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class FetchSGDStyle(_SketchBased):
    """Sketch-space EF + momentum (FetchSGD [36], which the paper cites for
    "momentum and error accumulation can be carried out within the data
    structure").

    State is TWO sketches (momentum + error), O(R*W) — independent of d.
    This is the memory-free alternative to gs-SGD's O(d) error-feedback
    accumulator (relevant at 235B params where the EF vector is GBs; see
    DESIGN.md §4). No exact second round: applied values come from the
    sketch estimates, and the error sketch subtracts the *applied* update
    (linearity), keeping the bookkeeping exact in sketch space.

    Momentum lives in the sketch — run under an optimizer WITHOUT its own
    momentum (e.g. sgdm(momentum=0)).
    """

    momentum: float = 0.9
    name: str = "fetchsgd"

    def init(self, d: int):
        z = jnp.zeros((self.sketch.rows, self.sketch.width), jnp.float32)
        return (z, z)  # (momentum sketch, error sketch)

    def comm_stats(self, d: int, nworkers: int) -> CommStats:
        return CommStats(
            _ring_allreduce_bytes(self.sketch.size * _F32, nworkers),
            rounds=2 * (nworkers - 1), label=self.name)

    def step(self, state, g: Array, *, axis: AxisNames, nworkers: int,
             key: Array | None = None):
        s_m, s_e = state
        d = g.shape[0]
        with obtrace.phase("encode"):
            sk = self._encode(g)
        with obtrace.phase("comm", "allreduce"):
            sk = jax.lax.psum(sk, axis)                # merged grad sketch
        with obtrace.phase("recover"):
            s_m = self.momentum * s_m + sk             # momentum in-sketch
            s_e = s_e + s_m                            # error accumulation
            idx, est = hm.heavymix(self.sketch, s_e, self.k, d, key=key,
                                   use_pallas=self.use_pallas or None)
            upd = _scatter(d, idx, est)
        with obtrace.phase("encode", "encode/applied"):
            s_e = s_e - self._encode(upd)              # subtract applied
        return upd, (s_m, s_e), self.comm_stats(d, nworkers)


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class SignSGD:
    """1-bit SGD with error feedback (paper Sec. II related work [30][31]).

    Transmits sign(u) plus one scale (mean |u|) per worker; EF keeps the
    quantization residual. Wire: d/8 bytes + 4 — the quantization-family
    baseline the paper contrasts sparsification against (<=32x max ratio).
    """

    name: str = "signsgd"

    def init(self, d: int) -> Array:
        return ef.init(d)

    def comm_stats(self, d: int, nworkers: int) -> CommStats:
        return CommStats(
            _ring_allreduce_bytes(d / 8 + _F32, nworkers),
            rounds=2 * (nworkers - 1), label=self.name)

    def step(self, acc: Array, g: Array, *, axis: AxisNames, nworkers: int,
             key: Array | None = None):
        u = ef.add(acc, g)
        scale = jnp.mean(jnp.abs(u))
        local = jnp.sign(u) * scale
        upd = jax.lax.psum(local, axis)
        acc = ef.residual_dense(u, local)
        return upd, acc, self.comm_stats(g.size, nworkers)


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class PowerSGD:
    """Rank-r low-rank compression with EF (paper Sec. II [27]).

    The flat gradient is matricized to a near-square (m, d/m) view and
    compressed with one power iteration (P = M Q, orthonormalize after a
    psum, Q' = M^T P̂) — two small all-reduces of r*(m+n) floats. Our flat
    layout matricizes the whole model at once (documented simplification
    of the per-layer original; the rank-r subspace spans layers).
    """

    rank: int = 4
    seed: int = 0
    name: str = "powersgd"

    def init(self, d: int):
        m = 1 << ((d - 1).bit_length() + 1) // 2       # near-square split
        n = (d + m - 1) // m
        q = jax.random.normal(jax.random.PRNGKey(self.seed), (n, self.rank),
                              jnp.float32)
        return (ef.init(d), q)

    def comm_stats(self, d: int, nworkers: int) -> CommStats:
        m0 = 1 << ((d - 1).bit_length() + 1) // 2      # init's split
        n = (d + m0 - 1) // m0
        m = (d + n - 1) // n                           # step's matricization
        return CommStats(
            _ring_allreduce_bytes(self.rank * (m + n) * _F32, nworkers),
            rounds=4 * (nworkers - 1), label=self.name)

    def step(self, state, g: Array, *, axis: AxisNames, nworkers: int,
             key: Array | None = None):
        acc, q = state
        u = ef.add(acc, g)
        d = u.shape[0]
        n = q.shape[0]
        m = (d + n - 1) // n
        mat = jnp.pad(u, (0, m * n - d)).reshape(m, n)
        p = jax.lax.psum(mat @ q, axis)                # (m, r)
        p, _ = jnp.linalg.qr(p)                        # orthonormal basis
        q_new = jax.lax.psum(mat.T @ p, axis)          # (n, r)
        approx = (p @ q_new.T).reshape(-1)[:d]         # rank-r of the SUM
        # EF: each worker's applied share is ITS projection p p^T M_w
        # (these sum to ``approx`` — same bookkeeping exactness as gs-SGD)
        local = (p @ (mat.T @ p).T).reshape(-1)[:d]
        acc = ef.residual_dense(u, local)
        return approx, (acc, q_new), self.comm_stats(d, nworkers)


# ---------------------------------------------------------------------------
# Bucketed compression (comm/compute-overlap pipeline; see DESIGN.md §5).
#
# The flat gradient is split into contiguous buckets at FlatSpec segment
# boundaries (``models.flatten.bucket_sizes``); each bucket gets its own
# compressor instance with proportionally scaled geometry and its own EF
# state. Buckets touch disjoint coordinate ranges, so their exchange chains
# are independent — the property the overlap scheduler in ``core/gs_sgd.py``
# exploits. With a single bucket the wrapper degenerates to the base
# compressor exactly (same geometry, same numerics).
# ---------------------------------------------------------------------------


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class BucketSpec:
    """Static contiguous partition of a flat d-vector."""

    sizes: tuple[int, ...]

    @property
    def n(self) -> int:
        return len(self.sizes)

    @property
    def total(self) -> int:
        return sum(self.sizes)

    @property
    def offsets(self) -> tuple[int, ...]:
        out, off = [], 0
        for s in self.sizes:
            out.append(off)
            off += s
        return tuple(out)

    def split(self, g: Array) -> list[Array]:
        return [jax.lax.slice_in_dim(g, o, o + s)
                for o, s in zip(self.offsets, self.sizes)]

    def join(self, parts) -> Array:
        return jnp.concatenate(list(parts))


def even_bucket_sizes(d: int, n: int) -> tuple[int, ...]:
    """~Equal split for callers without FlatSpec boundaries (benchmarks)."""
    n = max(1, min(int(n), int(d)))
    base, rem = divmod(int(d), n)
    return tuple(base + (1 if i < rem else 0) for i in range(n))


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class BucketedCommStats:
    """Per-bucket CommStats plus the aggregate view benchmarks consume."""

    per_bucket: tuple[CommStats, ...]
    label: str = "bucketed"

    @property
    def bytes_out(self) -> float:
        return sum(s.bytes_out for s in self.per_bucket)

    @property
    def rounds(self) -> int:
        return sum(s.rounds for s in self.per_bucket)

    def time(self, alpha: float, beta: float) -> float:
        """Serial (non-overlapped) Eq.1 time: buckets exchanged back-to-back.

        For the overlapped schedule, feed per-bucket times into
        ``overlap_schedule_time`` (as the benchmarks do)."""
        return sum(s.time(alpha, beta) for s in self.per_bucket)


def _pipeline_chains(t_compute, t_comm, ready) -> tuple[float, float]:
    """(encode-chain end, comm-chain end) of the bucket pipeline: bucket
    i's encode starts once its input is ready and the previous encode
    finished; its comm starts when both its encode and bucket i-1's comm
    have finished — the classic pipeline recurrence. The single source of
    the recurrence for ``overlap_schedule_time`` /
    ``interleaved_schedule_time`` / the sim replay."""
    done_enc = done_comm = 0.0
    for tc, tm, rd in zip(t_compute, t_comm, ready):
        done_enc = max(done_enc, float(rd)) + float(tc)
        done_comm = max(done_comm, done_enc) + float(tm)
    return done_enc, done_comm


def overlap_schedule_time(t_compute, t_comm,
                          ready=None) -> tuple[float, float]:
    """(serial, pipelined) totals for the encode->comm bucket pipeline.

    Serial = all stages back-to-back; pipelined = the comm chain's end
    under ``_pipeline_chains``. The saving is 0 for a single bucket.

    ready: optional per-bucket gradient-readiness times (monotone
    nondecreasing, e.g. (i+1)/N of backward) for modeling a
    backward-interleaved schedule; the serial baseline then waits for the
    last bucket (= full backward) before encoding. None = inputs ready at
    t=0 (the shipped post-accumulation schedule).
    """
    t_compute = [float(t) for t in t_compute]
    t_comm = [float(t) for t in t_comm]
    ready = [0.0] * len(t_compute) if ready is None else [
        float(r) for r in ready]
    serial = (ready[-1] if ready else 0.0) + sum(t_compute) + sum(t_comm)
    _, done_comm = _pipeline_chains(t_compute, t_comm, ready)
    return serial, done_comm


_MIN_BUCKET_WIDTH = 256  # smallest usable sketch row (pow2)


def interleaved_schedule_time(t_compute, t_comm, ready, *,
                              t_backward: float | None = None
                              ) -> tuple[float, float, float, float]:
    """3-stage backward/encode/comm recurrence of the readiness scheduler.

    Models ``core/gs_sgd.exchange_interleaved``: stage 0 is the backward
    scan, which emits bucket i's gradient at ``ready[i]`` (any order —
    buckets are re-sorted into readiness order here, exactly the order the
    real scheduler exchanges them); stage 1 is the per-bucket encode chain
    (one encode at a time, starting once the bucket is ready and the
    previous encode finished); stage 2 is the comm chain (a bucket's
    all-reduce starts when its encode and the previous bucket's comm are
    done).

    Returns ``(serial, pipelined, exposed, enc_done)``: serial is the
    post-accumulation baseline (full backward, then every stage
    back-to-back); pipelined is when the last comm finishes; exposed is
    the wall-clock the exchange adds past the end of backward
    (``t_backward``, default ``max(ready)``) — the quantity interleaving
    exists to shrink; enc_done is the encode chain's end (the sim replay
    splits exposed into encode/comm overhang with it). ``chunks=1`` (all
    ready at t_backward) reduces to ``overlap_schedule_time`` shifted by
    t_backward.
    """
    order = sorted(range(len(ready)), key=lambda i: (ready[i], i))
    tc = [float(t_compute[i]) for i in order]
    tm = [float(t_comm[i]) for i in order]
    rd = [float(ready[i]) for i in order]
    serial = (rd[-1] if rd else 0.0) + sum(tc) + sum(tm)
    enc_done, pipelined = _pipeline_chains(tc, tm, rd)
    t_b = (max(rd) if rd else 0.0) if t_backward is None else float(t_backward)
    return serial, pipelined, max(0.0, pipelined - t_b), enc_done


def fused_interleaved_schedule_time(piece_bucket, piece_compute, piece_ready,
                                    t_comm, *,
                                    t_backward: float | None = None
                                    ) -> tuple[float, float, float, float]:
    """Fused-encode variant of ``interleaved_schedule_time``.

    The encode chain's work items are bucket FRAGMENTS (one per VJP chunk
    overlapping the bucket), not whole buckets: fragment f of bucket
    ``piece_bucket[f]`` becomes ready at ``piece_ready[f]`` and costs
    ``piece_compute[f]`` to partial-encode; a bucket's wire sketch exists
    once its LAST fragment's encode finishes. The comm chain is unchanged
    (sketches still ship per bucket, in bucket-readiness order — the order
    ``exchange_interleaved`` fires all-reduces).

    Fragments encode in readiness order (ties broken toward the
    earlier-complete bucket, matching the scheduler's emission order).
    With exactly one fragment per bucket this reduces bit-for-bit to
    ``interleaved_schedule_time`` — same sort keys, same recurrences.

    Returns the same ``(serial, pipelined, exposed, enc_done)`` tuple.
    """
    n = len(t_comm)
    bucket_ready = [0.0] * n  # when the bucket's LAST fragment emits
    for b, rd in zip(piece_bucket, piece_ready):
        bucket_ready[b] = max(bucket_ready[b], float(rd))
    order = sorted(range(len(piece_ready)),
                   key=lambda f: (piece_ready[f],
                                  bucket_ready[piece_bucket[f]],
                                  piece_bucket[f], f))
    done_enc = 0.0
    enc_done_b = [0.0] * n
    for f in order:
        done_enc = max(done_enc, float(piece_ready[f])) + float(
            piece_compute[f])
        enc_done_b[piece_bucket[f]] = done_enc
    comm_order = sorted(range(n), key=lambda b: (bucket_ready[b], b))
    done_comm = 0.0
    for b in comm_order:
        done_comm = max(done_comm, enc_done_b[b]) + float(t_comm[b])
    rd_max = max((float(r) for r in piece_ready), default=0.0)
    serial = (rd_max + sum(float(t) for t in piece_compute)
              + sum(float(t) for t in t_comm))
    t_b = rd_max if t_backward is None else float(t_backward)
    return serial, done_comm, max(0.0, done_comm - t_b), done_enc


def _scale_bucket(base, d_bucket: int, d_total: int, i: int):
    """Per-bucket compressor: k and sketch width scaled by the bucket's
    share of coordinates; per-bucket hash seed decorrelates collisions
    across buckets.

    Degenerate-geometry guards: a tiny bucket's scaled k is clamped to
    >= 1 (round() alone would hand a 0-k compressor to top_k and crash at
    trace time), and the width is snapped to the power-of-two FLOOR of the
    proportional share, never below ``_MIN_BUCKET_WIDTH`` — SketchConfig
    rounds widths UP, which for a just-over-a-power bucket share doubled
    the aggregate sketch payload versus the monolithic geometry.
    """
    frac = d_bucket / d_total
    out = base
    if hasattr(base, "k"):
        out = dataclasses.replace(
            out, k=max(1, min(d_bucket, round(base.k * frac))))
    if isinstance(base, _SketchBased):
        share = max(1.0, base.sketch.width * frac)
        width = 1 << int(math.floor(math.log2(share)))
        width = min(base.sketch.width, max(_MIN_BUCKET_WIDTH, width))
        sk = dataclasses.replace(base.sketch, width=width,
                                 seed=base.sketch.seed + i)
        out = dataclasses.replace(out, sketch=sk)
    return out


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class BucketedCompressor:
    """Base-compressor contract over a bucket partition.

    ``init`` returns one EF state per bucket; ``step`` runs the buckets
    back-to-back (the reference order — the overlapped schedule lives in
    ``core/gs_sgd.py`` and is numerically identical because buckets cover
    disjoint coordinates).
    """

    base: Any
    spec: BucketSpec
    parts: tuple[Any, ...]
    name: str = "bucketed"

    def init(self, d: int):
        if d != self.spec.total:
            raise ValueError(
                f"gradient dimension {d} does not match the bucket "
                f"partition total {self.spec.total}")
        return tuple(c.init(s) for c, s in zip(self.parts, self.spec.sizes))

    def comm_stats(self, d: int, nworkers: int) -> BucketedCommStats:
        if d != self.spec.total:
            raise ValueError(
                f"gradient dimension {d} does not match the bucket "
                f"partition total {self.spec.total}")
        return BucketedCommStats(
            tuple(c.comm_stats(s, nworkers)
                  for c, s in zip(self.parts, self.spec.sizes)),
            label=self.name)

    def step(self, state, g: Array, *, axis: AxisNames, nworkers: int,
             key: Array | None = None, **kw):
        if kw:  # e.g. include=: drop kwargs the base doesn't support, so a
            # dense/topk bucketed step ignores the straggler mask exactly
            # like the monolithic dense path does (mask-aware aggregation
            # is a sketch-compressor capability)
            import inspect
            accepted = inspect.signature(
                type(self.base).step).parameters
            kw = {k: v for k, v in kw.items() if k in accepted}
        upds, news, stats = [], [], []
        for i, (c, st, gb) in enumerate(
                zip(self.parts, state, self.spec.split(g))):
            # single bucket passes the key through untouched so the
            # documented buckets=1 == monolithic identity holds exactly
            kb = (key if key is None or self.spec.n == 1
                  else jax.random.fold_in(key, i))
            u, s, nfo = c.step(st, gb, axis=axis, nworkers=nworkers,
                               key=kb, **kw)
            upds.append(u)
            news.append(s)
            stats.append(nfo)
        return (self.spec.join(upds), tuple(news),
                BucketedCommStats(tuple(stats), label=self.name))


def bucketize(base, sizes) -> BucketedCompressor:
    """Wrap ``base`` over contiguous buckets of the given sizes.

    A single bucket reuses ``base`` unchanged — geometry (and therefore
    numerics) identical to the monolithic compressor.
    """
    spec = BucketSpec(tuple(int(s) for s in sizes))
    if spec.n == 1:
        parts: tuple[Any, ...] = (base,)
    else:
        parts = tuple(_scale_bucket(base, db, spec.total, i)
                      for i, db in enumerate(spec.sizes))
    return BucketedCompressor(base=base, spec=spec, parts=parts,
                              name=f"bucketed[{spec.n}]({base.name})")


def static_comm_stats(compressor, d: int, nworkers: int):
    """Wire model of one aggregation step WITHOUT running it.

    Every compressor's ``comm_stats(d, nworkers)`` returns the identical
    ``CommStats`` its ``step`` would (the step methods call the accessor —
    single source of the wire model), so launch/benchmark tooling can dump
    per-step comm volumes with zero probe traffic. ``compressor=None`` is
    the dense-psum baseline path of ``make_train_step``.
    """
    if compressor is None:
        return DenseAllReduce().comm_stats(d, nworkers)
    return compressor.comm_stats(d, nworkers)


REGISTRY = {
    "dense": DenseAllReduce,
    "topk": TopKCompressor,
    "gtopk": GTopK,
    "sketched-sgd": SketchedSGD,
    "gs-sgd": GsSGD,
    "fetchsgd": FetchSGDStyle,
    "signsgd": SignSGD,
    "powersgd": PowerSGD,
}


def make(name: str, **kw) -> Any:
    """Build a compressor by name; sketch geometry via rows/width/seed kw.

    Non-sketch compressors silently drop the sketch-geometry kwargs (and
    the k-free baselines drop ``k``), so one launcher/tuner kwarg dict can
    be threaded to any method."""
    cls = REGISTRY[name]
    if name in ("sketched-sgd", "gs-sgd", "fetchsgd"):
        sk = cs.SketchConfig(rows=kw.pop("rows", 5),
                             width=kw.pop("width", 16384),
                             seed=kw.pop("seed", 0))
        return cls(sketch=sk, **kw)
    fields = {f.name for f in dataclasses.fields(cls)}
    for geo in ("rows", "width", "seed"):
        if geo not in fields:
            kw.pop(geo, None)
    if name in ("dense", "signsgd", "powersgd"):
        kw.pop("k", None)
    return cls(**kw)
