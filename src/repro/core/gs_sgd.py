"""gs-SGD distributed train/serve steps (runs inside a manual shard_map).

This is the layer that composes the paper's technique with the model zoo,
the flat-parameter storage, the optimizer, and the mesh. Two storage modes
(``configs.DP_MODE`` picks per arch):

'dp' (paper-faithful):
    Parameter/optimizer/EF state replicated over the data-parallel axes
    ('data'[, 'pod']); model-sharded leaves live whole per model rank,
    TP-replicated leaves live sharded over 'model' and are all-gathered at
    use (see flatten.py — this makes every flat coordinate uniquely owned,
    so per-worker top-k selection cannot de-synchronize replicas, and the
    gather transpose sums TP gradients automatically). gs-SGD compresses
    the gradient exchange over ALL dp axes — exactly Alg. 1.

'fsdp' (beyond-paper, for >4B-param archs):
    State additionally sharded over the in-pod 'data' axis (ZeRO-3): the
    scan body all-gathers one cycle's bf16 weights, and backward's
    psum_scatter returns grads summed-over-'data' in storage layout. The
    in-pod reduction is therefore dense (fast ICI), and gs-SGD compresses
    the remaining *cross-pod* exchange — the slow link, which is precisely
    the regime (1 GbE) the paper targets. Single-pod fsdp has no
    compression axis: the step is dense and EF-free.

All collectives are explicit (lax.psum / all_gather inside shard_map); the
same step functions run under ``jax.vmap(..., axis_name=...)`` for the CPU
multi-worker simulations used in tests and convergence benches.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core import compression as comp
from repro.obs import trace as obtrace
from repro.models.common import ArchConfig, ShardCtx, stored_experts
from repro.models.flatten import (SEG_NAMES, BucketPlan, FlatSpec,
                                  bucket_plan, bucket_sizes, make_flat_spec,
                                  pack_segs, packed_offsets, unpack_segs)
from repro.models import model as mdl
from repro.models import moe as moe_lib
from repro.optim.optimizers import Optimizer

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class MeshAxes:
    """Static description of the mesh the step runs in."""

    tp: int                       # size of the 'model' axis
    data: int                     # size of the 'data' axis
    pod: int = 1                  # size of the 'pod' axis (1 = single pod)
    tp_axis: str | None = "model"
    data_axis: str | None = "data"  # None -> single-device smoke path
    pod_axis: str | None = None   # None on single-pod meshes

    @property
    def dp_axes(self) -> tuple[str, ...]:
        axes = (self.pod_axis,) if self.pod_axis else ()
        return axes + ((self.data_axis,) if self.data_axis else ())

    @property
    def dp_size(self) -> int:
        return self.pod * self.data

    def ctx(self, dtype=jnp.bfloat16, comm_dtype=None) -> ShardCtx:
        return ShardCtx(tp=self.tp, tp_axis=self.tp_axis,
                        dp_axes=self.dp_axes, dtype=dtype,
                        comm_dtype=comm_dtype)


def _gather_closures(ma: MeshAxes, dp_mode: str, dtype):
    """(gather_sharded, gather_replicated) for the storage layout.

    Casts to the compute dtype BEFORE gathering (halves collective bytes);
    the autodiff transpose casts the f32 cotangent back after psum_scatter.
    """
    def gmodel(v):
        if ma.tp_axis is None:
            return v
        return jax.lax.all_gather(v, ma.tp_axis, axis=0, tiled=True)

    def gdata(v):
        if ma.data_axis is None:
            return v
        return jax.lax.all_gather(v, ma.data_axis, axis=0, tiled=True)

    cast = lambda v: v.astype(dtype)  # noqa: E731
    if dp_mode == "dp":
        return (lambda v: cast(v)), (lambda v: gmodel(cast(v)))
    if dp_mode == "fsdp":
        return (lambda v: gdata(cast(v))), (lambda v: gdata(gmodel(cast(v))))
    raise ValueError(f"unknown dp_mode {dp_mode!r}")


def seg_divisors(ma: MeshAxes, dp_mode: str) -> dict[str, int]:
    """By how much each stored segment's last dim is divided on-device."""
    d = 1 if dp_mode == "dp" else ma.data
    return {"top_s": d, "top_r": d * ma.tp,
            "cycles_s": d, "cycles_r": d * ma.tp}


def local_seg_shapes(fs: FlatSpec, ma: MeshAxes,
                     dp_mode: str) -> dict[str, tuple[int, ...]]:
    div = seg_divisors(ma, dp_mode)
    out = {}
    for k, shape in fs.seg_shapes().items():
        if shape[-1] % div[k] != 0:
            raise ValueError(
                f"segment {k!r} last dim {shape[-1]} is not divisible by "
                f"its on-device divisor {div[k]} (shape {shape})")
        out[k] = shape[:-1] + (shape[-1] // div[k],)
    return out


def validate_exchange_config(*, microbatch: int | None = None,
                             bwd_chunks: int | None = None,
                             fuse_encode: bool = False,
                             compressor: str = "gs-sgd",
                             buckets: int | None = None,
                             overlap: bool = True) -> None:
    """Reject exchange configs the runtime cannot build.

    The constraint itself lives in ``repro.api.spec.check_exchange_config``
    — the spec layer's central validation — so ``make_train_step``, every
    spec-driven CLI, and ``repro.tune``'s searcher (which SKIPs the
    candidate instead of crashing mid-sweep) all reject the combo with the
    identical message.
    """
    from repro.api.spec import check_exchange_config
    check_exchange_config(microbatch=microbatch, bwd_chunks=bwd_chunks,
                          fuse_encode=fuse_encode, compressor=compressor,
                          buckets=buckets, overlap=overlap)


# ---------------------------------------------------------------------------
# Bucket scheduler (comm/compute overlap; see DESIGN.md §5)
# ---------------------------------------------------------------------------


def exchange_bucketed(bc: "comp.BucketedCompressor", ef_state, g_flat,
                      *, axis, nworkers: int, overlap: bool = True,
                      key=None, include=None):
    """Run a bucketed gradient exchange, optionally software-pipelined.

    overlap=False (or a non-staged base compressor): buckets are exchanged
    strictly back-to-back via ``BucketedCompressor.step`` — the reference
    order the equivalence tests pin down.

    overlap=True emits the skewed schedule

        encode(0); for i: reduce(i); encode(i+1); recover(i)

    so bucket i's sketch all-reduce has NO data dependence on bucket i+1's
    encode: on TPU, XLA's latency-hiding scheduler runs the collective
    concurrently with the next bucket's compute (and, because each bucket's
    chain depends only on its own slice of the accumulated gradient, the
    first bucket's exchange is not serialized behind the full flat pack).
    On CPU the same program executes sequentially. Buckets cover disjoint
    coordinate ranges, so both orders are numerically identical.
    """
    n = bc.spec.n
    staged = all(hasattr(c, "stage_encode") for c in bc.parts)
    if not overlap or n == 1 or not staged:
        kw = {} if include is None else {"include": include}
        return bc.step(ef_state, g_flat, axis=axis, nworkers=nworkers,
                       key=key, **kw)

    parts = bc.spec.split(g_flat)
    keys = [None if key is None else jax.random.fold_in(key, i)
            for i in range(n)]
    us: list = [None] * n
    sks: list = [None] * n
    outs: list = [None] * n
    with obtrace.phase("encode", "encode/b0") as sp:
        us[0], sks[0] = bc.parts[0].stage_encode(ef_state[0], parts[0])
        sp.sync(sks[0])
    for i in range(n):
        with obtrace.phase("comm", f"allreduce/b{i}") as sp:
            sk_sum, scale = bc.parts[i].stage_reduce(
                sks[i], axis=axis, nworkers=nworkers, include=include)
            sp.sync(sk_sum)
        if i + 1 < n:  # next bucket's encode — independent of the reduce
            with obtrace.phase("encode", f"encode/b{i + 1}") as sp:
                us[i + 1], sks[i + 1] = bc.parts[i + 1].stage_encode(
                    ef_state[i + 1], parts[i + 1])
                sp.sync(sks[i + 1])
        with obtrace.phase("recover", f"recover/b{i}") as sp:
            outs[i] = bc.parts[i].stage_recover(
                us[i], sk_sum, scale, axis=axis, nworkers=nworkers,
                key=keys[i], include=include)
            sp.sync(outs[i][0])
    upd = bc.spec.join([o[0] for o in outs])
    ef_new = tuple(o[1] for o in outs)
    stats = comp.BucketedCommStats(tuple(o[2] for o in outs),
                                   label=bc.name + "|overlap")
    return upd, ef_new, stats


def exchange_interleaved(bc: "comp.BucketedCompressor", plan: BucketPlan,
                         ef_state, bwd_steps, top_grads, shapes: dict, *,
                         axis, nworkers: int, key=None, include=None,
                         fuse_encode: bool = False):
    """Readiness-driven bucketed exchange interleaved with backward chunks.

    Drives the backward itself: ``bwd_steps`` / ``top_grads`` come from
    ``model.chunked_loss_vjp`` and emit gradient slices in reverse-chunk
    order (embed+head last). After each emission event, every bucket whose
    packed coordinate range is now complete (``plan.readiness``) is
    assembled, encoded, and its sketch all-reduce issued — while the
    remaining chunks' backward VJPs are still ahead in program order, so
    XLA's latency-hiding scheduler can run the collective under backward
    compute. Recovery is skewed one bucket behind (the DESIGN.md §5
    pattern, now fed by §7's readiness events):

        bwd(K-1); enc(b0); red(b0); bwd(K-2); enc(b1); red(b1); rec(b0); ...

    Buckets cover disjoint coordinate ranges and each bucket's chain is
    the SAME ops as ``exchange_bucketed``'s (same geometry, same per-bucket
    key fold by packed index), so numerics are identical to the
    post-accumulation scheduler for any chunk count — pinned bit-exactly
    at ``chunks=1`` by tests/test_readiness.py. Returns (upd_sum, ef_new,
    BucketedCommStats) with buckets in packed order.

    fuse_encode=True (DESIGN.md §7, fused formulation): instead of holding
    each emitted slice until its bucket completes and then encoding the
    assembled range, every slice is EF-added and partial-encoded the moment
    it emits (``stage_encode_partial`` with the slice's offset inside its
    bucket); at the bucket's readiness event the partial sketches are
    summed (count-sketch linearity) and cast to the wire dtype
    (``stage_encode_merge``). The encode cost rides under the remaining
    backward chunks instead of serializing at the readiness event. Buckets
    whose compressor cannot fuse (no ``can_fuse``, e.g. the 'ts' encoder
    or a dense baseline) silently keep the assemble-then-encode path.
    """
    parts, spec = bc.parts, bc.spec
    n = spec.n
    offs = packed_offsets(shapes)
    f_cs = int(shapes["cycles_s"][-1])
    f_cr = int(shapes["cycles_r"][-1])
    by_event: dict[int, list[int]] = {}
    for i in plan.order:
        by_event.setdefault(plan.readiness[i], []).append(i)

    fusable = [bool(fuse_encode and getattr(p, "can_fuse", False)
                    and hasattr(p, "stage_encode_partial")) for p in parts]
    frags: list[list] = [[] for _ in range(n)]  # (off-in-bucket, u, sketch)

    pieces: list[tuple[int, Array]] = []   # (packed offset, flat grad slice)

    def fuse_piece(off: int, arr: Array) -> None:
        """Partial-encode the overlap of one emitted slice with every
        fusable bucket, at its offset inside that bucket."""
        for i in range(n):
            if not fusable[i]:
                continue
            o, s = spec.offsets[i], spec.sizes[i]
            lo, hi = max(o, off), min(o + s, off + arr.shape[0])
            if lo < hi:
                g_piece = jax.lax.slice_in_dim(arr, lo - off, hi - off)
                acc_piece = jax.lax.slice_in_dim(ef_state[i], lo - o, hi - o)
                u_piece, sk = parts[i].stage_encode_partial(
                    acc_piece, g_piece, lo - o)
                frags[i].append((lo - o, u_piece, sk))

    def emit(off: int, arr: Array) -> None:
        pieces.append((off, arr))
        fuse_piece(off, arr)

    def assemble(i: int) -> Array:
        o, s = spec.offsets[i], spec.sizes[i]
        got = []
        for off, arr in pieces:
            lo, hi = max(o, off), min(o + s, off + arr.shape[0])
            if lo < hi:
                got.append((lo, jax.lax.slice_in_dim(arr, lo - off, hi - off)))
        got.sort(key=lambda t: t[0])
        if sum(a.shape[0] for _, a in got) != s:
            raise ValueError(
                f"bucket {i} (offset {o}, size {s}) is not covered by the "
                "emitted gradient slices at its readiness event")
        return got[0][1] if len(got) == 1 else jnp.concatenate(
            [a for _, a in got])

    us: list = [None] * n
    sk_sum: list = [None] * n
    scale: list = [None] * n
    outs: list = [None] * n
    launched: list[int] = []
    tr = obtrace.current()

    def recover(i: int) -> None:
        kb = (key if key is None or n == 1
              else jax.random.fold_in(key, i))
        with obtrace.phase("recover", f"recover/b{i}") as sp:
            outs[i] = parts[i].stage_recover(
                us[i], sk_sum[i], scale[i], axis=axis, nworkers=nworkers,
                key=kb, include=include)
            sp.sync(outs[i][0])

    n_chunks = len(bwd_steps)
    for ev in range(plan.n_events):
        if ev < n_chunks:
            with tr.span(f"backward/chunk{ev}", cat="backward") as sp:
                (a, b), d_cs, d_cr = bwd_steps[ev]()
                sp.sync((d_cs, d_cr))
            if d_cs.size:
                emit(offs["cycles_s"] + a * f_cs, d_cs.reshape(-1))
            if d_cr.size:
                emit(offs["cycles_r"] + a * f_cr, d_cr.reshape(-1))
        if ev == n_chunks - 1:  # top segments finalize with the last chunk
            with tr.span("backward/top", cat="backward") as sp:
                d_ts, d_tr = top_grads()
                sp.sync((d_ts, d_tr))
            if d_ts.size:
                emit(offs["top_s"], d_ts.reshape(-1))
            if d_tr.size:
                emit(offs["top_r"], d_tr.reshape(-1))
        for i in by_event.get(ev, []):
            tr.instant(f"ready/b{i}", cat="encode",
                       args={"bucket": i, "event": ev})
            with obtrace.phase("encode", f"encode/b{i}") as sp:
                if fusable[i]:
                    us[i], sk = parts[i].stage_encode_merge(frags[i])
                else:
                    us[i], sk = parts[i].stage_encode(ef_state[i],
                                                      assemble(i))
                sp.sync(sk)
            with obtrace.phase("comm", f"allreduce/b{i}") as sp:
                sk_sum[i], scale[i] = parts[i].stage_reduce(
                    sk, axis=axis, nworkers=nworkers, include=include)
                sp.sync(sk_sum[i])
            launched.append(i)
            while len(launched) > 1:  # recover, one bucket behind
                recover(launched.pop(0))
    for i in launched:
        recover(i)
    upd = spec.join([outs[i][0] for i in range(n)])
    ef_new = tuple(outs[i][1] for i in range(n))
    stats = comp.BucketedCommStats(tuple(outs[i][2] for i in range(n)),
                                   label=bc.name + "|interleaved")
    return upd, ef_new, stats


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TrainStep:
    """Bound train step + its static metadata (comm stats, state builder)."""

    fn: Callable[..., tuple[Any, dict]]
    fs: FlatSpec
    ma: MeshAxes
    dp_mode: str
    compressor: Any | None
    d_local: int                  # flat coords per device (compressor input)
    n_buckets: int = 1            # gradient-exchange buckets (1 = monolithic)
    overlap: bool = True          # pipelined bucket schedule (n_buckets > 1)
    bwd_chunks: int = 0           # backward chunks (0 = monolithic backward)
    plan: BucketPlan | None = None  # readiness plan (bwd_chunks > 0)
    fuse_encode: bool = False     # fragment-wise encode in the interleave

    def init_state(self, key: Array, opt: Optimizer) -> Any:
        """Concrete state for single-device (tp=1, dp=1) smoke/test runs."""
        from repro.models.flatten import init_flat_params
        if self.ma.tp != 1 or self.ma.dp_size != 1:
            raise ValueError(
                "init_state builds single-device state only (tp=1, dp=1); "
                f"got tp={self.ma.tp}, dp={self.ma.dp_size}")
        params = init_flat_params(self.fs.cfg, key, 1, self.fs)
        return make_state(params, opt, self.compressor, self.d_local,
                          cfg=self.fs.cfg)


def make_state(params: dict, opt: Optimizer, compressor, d_local: int,
               ef_dtype=jnp.float32, cfg: ArchConfig | None = None) -> dict:
    """Step-0 state. With ``cfg`` it also holds what the model keeps
    outside its parameters: a sigmoid router's selection bias (out of the
    gradient, the optimizer and the exchanged vector)."""
    opt_state = {k: opt.init(v.shape) for k, v in params.items()}
    ef = (compressor.init(d_local) if compressor is not None else
          jnp.zeros((0,), jnp.float32))
    if compressor is not None and ef_dtype != jnp.float32:
        ef = jax.tree_util.tree_map(lambda a: a.astype(ef_dtype), ef)
    state = {"params": params, "opt": opt_state, "ef": ef,
             "step": jnp.int32(0)}
    bias = None if cfg is None else moe_lib.init_router_bias(cfg)
    if bias is not None:
        state["router_bias"] = bias
    return state


def make_train_step(cfg: ArchConfig, ma: MeshAxes, opt: Optimizer, *,
                    dp_mode: str = "dp",
                    spec: Any | None = None,
                    compressor_name: str | None = "gs-sgd",
                    compressor_kw: dict | None = None,
                    remat: bool = True, dtype=jnp.bfloat16,
                    microbatch: int | None = None,
                    clip_norm: float | None = None,
                    fs: FlatSpec | None = None,
                    buckets: int | None = None,
                    overlap: bool = True,
                    bwd_chunks: int | None = None,
                    fuse_encode: bool = False) -> TrainStep:
    """Build the per-device train step (to be wrapped in shard_map/vmap).

    spec: a ``repro.api.ExchangeSpec`` — the spec-first entry every CLI
    uses. The compressor name, resolved sketch geometry (via the one
    ``SketchSpec`` default table at this step's ``d_local``), bucket/
    overlap/readiness schedule, microbatch, and wire knobs all come from
    the spec; the legacy kwargs below are a thin shim over the same body
    and must be left at their defaults when ``spec`` is passed.

    compressor_name=None or 'dense' -> dense psum baseline. In fsdp mode
    the compression axis is the pod axis only (grads arrive pre-reduced
    over 'data'); a single-pod fsdp step is dense regardless.

    microbatch: per-device rows per gradient-accumulation slice (None =
    whole local batch in one shot). Compression/optimizer run ONCE per
    step on the accumulated gradient — faithful to Alg. 1's per-iteration
    semantics regardless of accumulation.

    buckets: None -> monolithic exchange (the seed path). An int routes the
    exchange through the bucketed pipeline: the flat gradient is split at
    FlatSpec segment boundaries into ~``buckets`` contiguous buckets, each
    with its own EF state and proportionally scaled compressor geometry
    ('dense'/None baselines bucket their psum too, so comparisons share
    the schedule). buckets=1
    exercises the bucketed code path with numerics identical to monolithic.
    overlap: pipeline bucket i's all-reduce with bucket i+1's encode
    (numerically identical either way; see ``exchange_bucketed``).

    bwd_chunks: None -> monolithic backward (post-accumulation exchange,
    the PR 1 path). An int >= 1 splits the cycle scan into that many
    autodiff chunks (``model.chunked_loss_vjp``) and, when the exchange is
    bucketed, staged and overlap=True, drives the readiness scheduler
    ``exchange_interleaved`` — buckets begin their encode/all-reduce as the
    backward scan emits them (DESIGN.md §7). bwd_chunks=1 runs the
    readiness path with a single chunk: bit-exact vs the bwd_chunks=None
    step. Incompatible with ``microbatch`` (the exchange must see the one
    accumulated gradient it interleaves with).

    fuse_encode: partial-encode each emitted VJP fragment immediately
    (count-sketch linearity) instead of assemble-then-encode at the
    bucket's readiness event — gs-sgd with buckets + bwd_chunks +
    overlap only (validated); see ``exchange_interleaved``.
    """
    import math as _math

    fs = fs or make_flat_spec(cfg, ma.tp)
    ctx = ma.ctx(dtype)
    gathers = _gather_closures(ma, dp_mode, dtype)
    shapes = local_seg_shapes(fs, ma, dp_mode)
    d_local = sum(_math.prod(s) for s in shapes.values())
    if spec is not None:
        if (compressor_name != "gs-sgd" or compressor_kw is not None
                or microbatch is not None or buckets is not None
                or overlap is not True or bwd_chunks is not None
                or fuse_encode is not False):
            raise ValueError("make_train_step: pass either spec= or the "
                             "legacy exchange kwargs, not both")
        spec.validate()
        if spec.shape is not None:
            raise ValueError(
                f"collective shape {spec.shape!r} is a simulator-only "
                "knob — the training step cannot apply it (set shape to "
                "none, or use repro.launch.simulate)")
        compressor_name = (None if spec.compressor == "none"
                           else spec.compressor)
        compressor_kw = spec.compressor_kw(d_local) or None
        microbatch, buckets = spec.microbatch, spec.buckets
        overlap, bwd_chunks = spec.overlap, spec.bwd_chunks
        fuse_encode = spec.fuse_encode
    validate_exchange_config(
        microbatch=microbatch, bwd_chunks=bwd_chunks,
        fuse_encode=fuse_encode,
        compressor=compressor_name if compressor_name else "dense",
        buckets=buckets, overlap=overlap)

    # In 'dp' the compressor sums raw per-worker grads over all dp axes; in
    # 'fsdp' backward's psum_scatter has already summed over 'data', so only
    # the pod axis remains. Either way ``g_sum`` ends up as the SUM over
    # all dp_size workers and is divided once below.
    if dp_mode == "dp":
        comp_axes: tuple[str, ...] = ma.dp_axes
        comp_n = ma.dp_size
    else:
        comp_axes = (ma.pod_axis,) if ma.pod_axis else ()
        comp_n = ma.pod

    compressor = None
    plan = None
    bucketed = bool(buckets is not None and comp_axes)
    if comp_axes and (compressor_name not in (None, "dense") or bucketed):
        if compressor_name in (None, "dense"):
            # buckets= with the dense/None baseline: run the psum through
            # the bucketed schedule too, so baseline comparisons share it
            compressor = comp.make("dense")
        else:
            compressor = comp.make(compressor_name, **(compressor_kw or {}))
        if bucketed:
            plan = bucket_plan(shapes, buckets, bwd_chunks or 1)
            if plan.sizes != bucket_sizes(shapes, buckets):
                raise ValueError(
                    f"readiness plan bucket sizes {plan.sizes} disagree "
                    f"with the partition {bucket_sizes(shapes, buckets)}")
            compressor = comp.bucketize(compressor, plan.sizes)

    # Readiness interleave needs a staged bucketed compressor and the
    # pipelined schedule; otherwise a chunked backward still runs but the
    # exchange stays post-accumulation (gradient assembled after backward).
    interleave = (bwd_chunks is not None and plan is not None and overlap
                  and all(hasattr(c, "stage_encode")
                          for c in compressor.parts))

    def train_step(state: dict, batch: dict,
                   include: Array | None = None) -> tuple[dict, dict]:
        params, opt_state, ef, step = (state["params"], state["opt"],
                                       state["ef"], state["step"])
        bias = (state["router_bias"] if cfg.router == "sigmoid"  # its state
                else None)

        # The loss is replicated across the TP axis, so each rank seeds a
        # cotangent of 1 and the collective transposes (psum -> psum,
        # all_gather -> psum_scatter) compute the COMBINED objective's
        # gradient: d(sum_r L_r)/d(theta) = tp * dL/d(theta) — exactly tp x
        # too large (verified empirically in tests/test_tp.py). Seeding
        # with L/tp cancels it exactly; the reported value is scaled back.
        inv_tp = 1.0 / ma.tp

        def loss_of(p, b):
            # inside autodiff: the backward is this scope's transpose
            with jax.named_scope("forward"):
                loss, load = mdl.loss_fn(cfg, ctx, fs, p, b, gathers=gathers,
                                         remat=remat, router_bias=bias,
                                         with_load=True)
                return inv_tp * loss, load

        tr = obtrace.current()
        b_loc = batch["tokens"].shape[0]
        mb = microbatch or b_loc
        bwd_steps = top_grads = None
        if bwd_chunks is not None:
            # Chunked backward: per-chunk VJPs emit gradient slices in
            # reverse order (seeded with 1/tp, mirroring loss_of's scaling)
            with obtrace.phase("forward") as sp:
                loss, bwd_steps, top_grads, load = mdl.chunked_loss_vjp(
                    cfg, ctx, fs, params, batch, chunks=bwd_chunks,
                    gathers=gathers, remat=remat, grad_seed=inv_tp,
                    router_bias=bias)
                sp.sync(loss)
            loss = inv_tp * loss
            grads = None
        elif mb >= b_loc:
            # monolithic autodiff: forward and backward are one fused
            # call, so the span carries both under cat='backward'; it
            # enters no scope (loss_of names the forward, and the
            # backward is its transpose)
            with tr.span("loss_and_grad", cat="backward") as sp:
                (loss, load), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(params, batch)
                sp.sync(loss)
        else:
            if b_loc % mb != 0:
                raise ValueError(
                    f"local batch {b_loc} is not divisible by "
                    f"microbatch {mb}")
            n_mb = b_loc // mb
            slices = jax.tree_util.tree_map(
                lambda a: a.reshape((n_mb, mb) + a.shape[1:]), batch)

            def acc_body(carry, b):
                l_acc, g_acc = carry
                (l, load), g = jax.value_and_grad(loss_of, has_aux=True)(
                    params, b)
                g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
                return (l_acc + l, g_acc), load

            zeros = jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape, jnp.float32), params)
            (loss, grads), loads = jax.lax.scan(
                acc_body, (jnp.float32(0.0), zeros), slices)
            load = None if loads is None else jnp.sum(loads, axis=0)
            loss = loss / n_mb
            grads = jax.tree_util.tree_map(lambda g: g / n_mb, grads)

        def flat_of_chunks():
            # post-accumulation fallback for a chunked backward: drain the
            # VJP steps, reassemble pack_segs order (top_s, top_r, cycle
            # rows ascending per segment)
            cs_parts, cr_parts = [], []
            for step in bwd_steps:
                (a, _), d_cs, d_cr = step()
                cs_parts.append((a, d_cs))
                cr_parts.append((a, d_cr))
            d_ts, d_tr = top_grads()
            rows = lambda ps: [p.reshape(-1) for _, p in sorted(ps)]  # noqa: E731
            return jnp.concatenate([d_ts.reshape(-1), d_tr.reshape(-1)]
                                   + rows(cs_parts) + rows(cr_parts))

        kw = {"include": include} if include is not None else {}
        if compressor is not None:
            ef32 = jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32), ef)
            if interleave:
                upd, ef_new, _ = exchange_interleaved(
                    compressor, plan, ef32, bwd_steps, top_grads, shapes,
                    axis=comp_axes, nworkers=comp_n,
                    fuse_encode=fuse_encode, **kw)
            else:
                with jax.named_scope("encode"):   # the gradient pack
                    g_flat = (flat_of_chunks() if grads is None
                              else pack_segs(grads))
                if isinstance(compressor, comp.BucketedCompressor):
                    upd, ef_new, _ = exchange_bucketed(
                        compressor, ef32, g_flat, axis=comp_axes,
                        nworkers=comp_n, overlap=overlap, **kw)
                else:   # the compressor names its own stages
                    upd, ef_new, _ = compressor.step(
                        ef32, g_flat, axis=comp_axes, nworkers=comp_n,
                        **kw)
            ef_new = jax.tree_util.tree_map(
                lambda new, old: new.astype(old.dtype), ef_new, ef)
            g_sum = unpack_segs(upd, params)
        elif comp_axes:                    # dense baseline over dp axes
            with jax.named_scope("encode"):   # the gradient pack alone
                g_flat = (flat_of_chunks() if grads is None
                          else pack_segs(grads))
            with obtrace.phase("comm", "allreduce") as sp:
                upd = jax.lax.psum(g_flat, comp_axes)
                sp.sync(upd)
            ef_new = ef
            g_sum = unpack_segs(upd, params)
        else:   # one worker, or fsdp single-pod (summed over 'data'):
            # nothing to exchange, so no flat copy of the gradient
            g_sum = (unpack_segs(flat_of_chunks(), params) if grads is None
                     else grads)
            ef_new = ef

        # g_sum: the gradient summed over the dp_size workers, by segment
        with obtrace.phase("optimizer") as sp:
            g_mean = {k: g / ma.dp_size for k, g in g_sum.items()}
            gsq = sum(jnp.sum(g * g) for g in g_mean.values())
            # coords are disjoint across 'model' (and across 'data' in fsdp)
            norm_axes = tuple(a for a in (
                ma.tp_axis, ma.data_axis if dp_mode == "fsdp" else None)
                if a)
            if norm_axes:
                gsq = jax.lax.psum(gsq, norm_axes)
            gnorm = jnp.sqrt(gsq)
            if clip_norm is not None:  # global-norm clip, aggregated grad
                scale = jnp.minimum(1.0, clip_norm / jnp.maximum(gnorm,
                                                                 1e-12))
                g_mean = {k: g * scale for k, g in g_mean.items()}
            new_params, new_opt = {}, {}
            for k in SEG_NAMES:
                new_params[k], new_opt[k] = opt.apply(params[k], g_mean[k],
                                                      opt_state[k], step)
            sp.sync(new_params["top_s"])

        loss = loss * ma.tp  # undo the grad-seed scaling for reporting
        loss_rep = jax.lax.pmean(loss, ma.dp_axes) if ma.dp_axes else loss
        new_state = {"params": new_params, "opt": new_opt, "ef": ef_new,
                     "step": step + 1}
        metrics = {"loss": loss_rep, "grad_norm": gnorm}
        if load is not None:
            metrics.update(moe_counters(cfg, ctx, load))
        if bias is not None:
            total = jax.lax.psum(load, ma.dp_axes) if ma.dp_axes else load
            new_state["router_bias"] = moe_lib.update_bias(cfg, bias, total)
        return new_state, metrics

    return TrainStep(fn=train_step, fs=fs, ma=ma, dp_mode=dp_mode,
                     compressor=compressor, d_local=d_local,
                     n_buckets=(compressor.spec.n
                                if isinstance(compressor,
                                              comp.BucketedCompressor) else 1),
                     overlap=overlap, bwd_chunks=(bwd_chunks or 0),
                     plan=plan, fuse_encode=fuse_encode)


def moe_counters(cfg: ArchConfig, ctx: ShardCtx, load: Array) -> dict:
    """This worker's step counters of its held experts, from the tokens
    each MoE cycle routed to each expert (n_cycles, E): the rows routed
    to held experts, over every cycle, and the largest held expert's
    load over the mean held load of its cycle."""
    with jax.named_scope("moe_route"):
        n = stored_experts(cfg, ctx.tp) // ctx.tp
        first = ctx.tp_rank() * n
        held = jax.lax.dynamic_slice_in_dim(load, first, n, axis=1)
        mean = jnp.maximum(jnp.mean(held, axis=1), 1e-9)
        return {"moe_held_rows": jnp.sum(held),
                "moe_held_max_over_mean": jnp.max(jnp.max(held, 1) / mean)}


# ---------------------------------------------------------------------------
# Serve steps
# ---------------------------------------------------------------------------


def make_serve_fns(cfg: ArchConfig, ma: MeshAxes, *, dp_mode: str = "dp",
                   dtype=jnp.bfloat16, comm_dtype=None,
                   fs: FlatSpec | None = None):
    """(prefill, decode) bound to the storage layout. Params segs only —
    no optimizer/EF state at serving time. comm_dtype=float8_e4m3fn puts
    the activation reductions on the wire in fp8 (4x fewer bytes)."""
    fs = fs or make_flat_spec(cfg, ma.tp)
    ctx = ma.ctx(dtype, comm_dtype)
    gathers = _gather_closures(ma, dp_mode, dtype)

    def prefill(params: dict, batch: dict, cache: Any):
        return mdl.prefill_fn(cfg, ctx, fs, params, batch, cache,
                              gathers=gathers)

    def decode(params: dict, tokens: Array, kv_len: Array, cache: Any,
               cross_kv: Array | None = None):
        return mdl.decode_fn(cfg, ctx, fs, params, tokens, kv_len, cache,
                             cross_kv=cross_kv, gathers=gathers)

    return prefill, decode
