"""GSPMD shifting-buffer SWARM pipeline over the ``pod`` mesh axis.

The elastic layer (``repro.core``) simulates SWARM's stochastic wiring;
this module is the *compiled* counterpart for one static configuration:
all pipeline stages live in one jitted step, stage-stacked parameters are
sharded over ``pod``, and microbatch activations travel between stages
through a shifting buffer — ``jnp.roll`` on the stage dim, which GSPMD
lowers to a collective-permute (Xu et al., 2021; the same construction
Praxis calls a layerwise-shardable pipeline).

Schedule: with S stages and M microbatches the loop runs ``T = M + S - 1``
ticks.  At tick ``t`` slot ``s`` holds microbatch ``t - s``; slot 0
ingests microbatch ``t`` (embedded on the fly), slot ``S-1`` emits
microbatch ``t - (S-1)`` into the loss.  Slots outside ``[0, M)`` compute
garbage that is never read — the cost of the classic ``(S-1)/T`` bubble.

Autodiff gives the reverse schedule for free: the transpose of the
buffer shift is the opposite shift, so gradients pipeline backwards
through the same buffer.  All four boundary-compression modes of
``cfg.boundary_compression`` run here (paper §4.3, App. J):

* ``int8`` — every live boundary crossing is blockwise-quantized in BOTH
  directions (activations forward, cotangents backward) via
  :func:`repro.compression.quant8.compress_boundary`;
* ``bottleneck`` / ``maxout`` — the learned codecs: the buffer itself is
  the wire, so it carries the compressed ``c``-dim tensor; sending stage
  ``b`` compresses with ``w_c[b]``, receiving stage ``b+1`` decompresses
  with ``w_d[b]`` (``params["boundary"]``, attached by
  ``repro.train.steps.model_specs`` when ``cfg.pipeline_stages > 1``).
  Both are ordinary trainable params: gradients flow into them through
  the shifted buffer and the optimizer updates them with everything else.

Equivalence to the plain step / to :func:`make_reference_loss_fn` (same
loss, same gradients, within f32 tolerance) is enforced by
``tests/test_distribution.py`` and ``tests/test_codecs.py`` on a 2x2x2
host-device mesh.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.compression import codecs
from repro.compression import quant8
from repro.dist.constrain import constrain
from repro.models import model as model_lib
from repro.models.blocks import REGISTRY
from repro.models.config import ArchConfig
from repro.models.stage_plan import StagePlan, get_stage_plan
from repro.optim.adamw import Optimizer

Tree = Any


def stage_periodic(cfg: ArchConfig, n_stages: int) -> bool:
    """Can this layer stack split into ``n_stages`` *identical* stages?

    The shifting-buffer pipeline vmaps ONE stage program over the stage
    dim, so every stage must run the same block-kind sequence:

    * encoder-decoder models (whisper) are never periodic — the two
      streams are structurally different;
    * ALBERT-style shared stacks are periodic iff the parameter groups
      split evenly (``share_groups % n_stages == 0``);
    * otherwise the block-kind pattern must tile: ``n_layers % n_stages
      == 0`` and each stage's slice of ``block_kinds`` identical (the
      xlstm (5 mLSTM, 1 sLSTM) x 2 arrangement is periodic at 2 stages;
      a 32-layer dense stack is not at 7).
    """
    if n_stages < 1:
        return False
    if cfg.family == "audio" or cfg.encoder_layers:
        return False
    try:
        return get_stage_plan(cfg, n_stages).periodic
    except ValueError:       # stack cannot split at this stage count
        return False


def _period_runs(cfg: ArchConfig, n_stages: int) -> list[tuple[str, int]]:
    """(kind, count) runs of ONE stage's slice of the layer pattern
    (periodic stacks: every stage's runs equal stage 0's)."""
    return list(get_stage_plan(cfg, n_stages).stages[0].runs)


def _stage_blocks(cfg: ArchConfig, blocks: Tree, n_stages: int) -> Tree:
    """Regroup ``params['blocks']`` (global layer stacks) into per-stage
    stacks: one tree per period run, leaves ``[n_stages, count, ...]``.

    Pure reshape for the common homogeneous cases.  For mixed-kind
    periodic patterns each (stage, period-run) segment is a contiguous
    same-kind layer range, so it sits inside exactly one maximal global
    run: a static slice of that run's stack, stacked across stages
    (differentiable, so gradients land back on the original stacks).
    """
    if cfg.share_groups:
        g = cfg.share_groups // n_stages
        return [jax.tree.map(
            lambda a: a.reshape(n_stages, g, *a.shape[1:]), blocks[0])]
    g_runs = model_lib.segments(cfg.block_kinds)
    per = cfg.n_layers // n_stages
    if len(g_runs) == 1:
        return [jax.tree.map(
            lambda a: a.reshape(n_stages, per, *a.shape[1:]), blocks[0])]
    starts = [0]
    for _, c in g_runs:
        starts.append(starts[-1] + c)
    out, off = [], 0
    for _, c in _period_runs(cfg, n_stages):
        stages = []
        for s in range(n_stages):
            lo_g = s * per + off                 # global start of the range
            ri = max(i for i in range(len(g_runs)) if starts[i] <= lo_g)
            lo = lo_g - starts[ri]
            stages.append(jax.tree.map(
                lambda a, _lo=lo: a[_lo:_lo + c], blocks[ri]))
        out.append(jax.tree.map(lambda *xs: jnp.stack(xs), *stages))
        off += c
    return out


def make_block_core(cfg: ArchConfig, runs: list[tuple[str, int]],
                    reps: int = 1, *, remat: bool = False):
    """The span-parameterized stage core: scan ``runs`` of stacked layer
    params over ``(x, aux)``.  ONE implementation shared by every
    execution path — the GSPMD tick below (via :func:`_make_stage_fn`),
    the sequential reference, and the per-stage / span programs of
    ``repro.runtime.stage_model`` — so a stage computes identical math
    whether it runs vmapped in the shifting buffer, alone on a peer, or
    fused inside a span.

    ``blocks_s`` is one stage's ``[tree-per-run]`` list (leaves stacked
    ``[count, ...]``); ``reps > 1`` re-applies each layer (ALBERT-style
    sharing, paper §4.3).  ``remat`` checkpoints every layer
    application, so the backward keeps one layer input per application
    (a shared stack's 16 reps included) and recomputes the rest.
    """
    def block_fn(blocks_s: Tree, x: jax.Array, aux: jax.Array, positions):
        for (kind, _), seg in zip(runs, blocks_s):
            apply_fn = functools.partial(REGISTRY[kind][1], cfg)
            if remat:
                apply_fn = jax.checkpoint(
                    apply_fn, policy=jax.checkpoint_policies.nothing_saveable)

            def body(carry, p_l, _apply=apply_fn):
                def rep(carry, _):
                    x, aux = carry
                    x, a = _apply(p_l, x, positions)
                    return (x, aux + a), None

                if reps == 1:
                    return rep(carry, None)
                # reps > 1: ALBERT sharing, as a scan so the shared
                # layer's weight gradient folds into one carry per rep
                # instead of every rep's contribution staying live
                return jax.lax.scan(rep, carry, None, length=reps)

            (x, aux), _ = jax.lax.scan(body, (x, aux), seg)
        return x, aux

    return block_fn


def _make_stage_fn(cfg: ArchConfig, n_stages: int, remat: bool):
    """One (periodic) stage's program for the vmapped shifting buffer."""
    spec = get_stage_plan(cfg, n_stages).stages[0]
    return make_block_core(cfg, list(spec.runs), spec.reps, remat=remat)


def _resolve_codec(cfg: ArchConfig, n_stages: int,
                   compress: Optional[str]) -> str:
    """Validated boundary-compression mode for an ``n_stages`` pipeline."""
    comp = codecs.resolve_mode(cfg, compress)
    if n_stages == 1:
        return "none"                    # no boundaries to compress
    if comp in codecs.LEARNED and cfg.pipeline_stages != n_stages:
        raise ValueError(
            f"{cfg.name}: compress={comp!r} needs one learned codec pair "
            f"per boundary — set cfg.pipeline_stages={n_stages} (got "
            f"{cfg.pipeline_stages}) so model_specs attaches "
            "params['boundary']")
    return comp


def boundary_crossing(cfg: ArchConfig, comp: str, bparams: Optional[Tree],
                      b: int, x: jax.Array) -> jax.Array:
    """What boundary ``b`` (stage b -> b+1) does to the activation, given
    the stage-stacked codec tree (``bparams`` leading dim = boundary
    index).  The codec-boundary core shared by the sequential reference
    and the span programs of ``repro.runtime.stage_model`` — on-device
    when the boundary is fused inside a span, on the wire otherwise.
    Routed through the ``cfg.kernels``-aware codec helpers, so under
    ``"pallas"`` the encode(+QDQ) and dequantize+decode sides each
    collapse to one fused kernel launch."""
    if comp == "int8":
        return codecs.int8_boundary(cfg, x)
    if comp in codecs.LEARNED:
        pb = jax.tree.map(lambda a: a[b], bparams)
        return codecs.decode_wire(
            cfg, comp, pb, codecs.encode_wire(cfg, comp, pb, x))
    return x


def _boundary_params(params: Tree, comp: str, n_stages: int) -> Tree:
    bparams = params.get("boundary")
    if bparams is None:
        raise ValueError(
            f"compress={comp!r} but params carry no 'boundary' codec tree "
            "— build the state from repro.train.steps.model_specs with "
            "cfg.pipeline_stages set")
    nb = jax.tree.leaves(bparams)[0].shape[0]
    if nb != n_stages - 1:
        raise ValueError(f"params['boundary'] holds {nb} codec pairs, "
                         f"need {n_stages - 1} (one per boundary)")
    return bparams


def make_pipeline_train_step(cfg: ArchConfig, optimizer: Optimizer,
                             n_stages: int, n_microbatches: int, *,
                             remat: bool | str = True,
                             compress: Optional[str] = None):
    """Build ``(state, batch) -> (state, {"loss", "ce"})`` — the pipelined
    twin of ``steps.make_train_step``.

    ``compress=None`` defers to ``cfg.boundary_compression``; all four
    modes run here — ``"none"``, ``"int8"``, and the learned
    ``"bottleneck"`` / ``"maxout"`` codecs (which require
    ``cfg.pipeline_stages == n_stages`` so the state carries
    ``params["boundary"]``).
    """
    if not stage_periodic(cfg, n_stages):
        raise ValueError(f"{cfg.name}: layer stack is not periodic at "
                         f"{n_stages} stages (see stage_periodic)")
    comp = _resolve_codec(cfg, n_stages, compress)
    do_remat = (remat != "none") if isinstance(remat, str) else bool(remat)
    stage_fn = _make_stage_fn(cfg, n_stages, do_remat)
    S_, M = n_stages, n_microbatches

    from repro.train import steps as steps_lib   # lazy: steps imports models

    def loss_fn(params: Tree, batch: Tree):
        tokens, labels = batch["tokens"], batch["labels"]
        B, S = tokens.shape
        if B % M:
            raise ValueError(f"batch {B} not divisible by {M} microbatches")
        mb = B // M
        tok_mb = tokens.reshape(M, mb, S)
        lab_mb = labels.reshape(M, mb, S)
        if "positions" in batch:                       # mrope: [3, B, S]
            p = batch["positions"]
            pos_mb = p.reshape(p.shape[0], M, mb, S).swapaxes(0, 1)
            pos_axis = 0
        else:
            pos_mb = model_lib.default_positions(cfg, mb, S)
            pos_axis = None                            # shared by all slots
        stage_blocks = [jax.tree.map(
            lambda a: constrain(a, "pod", *([None] * (a.ndim - 1))), t)
            for t in _stage_blocks(cfg, params["blocks"], S_)]
        v_stage = jax.vmap(stage_fn, in_axes=(0, 0, 0, pos_axis))
        bparams = (_boundary_params(params, comp, S_)
                   if comp in codecs.LEARNED else None)
        wdim = codecs.wire_dim(cfg, comp)

        def encode(outs):
            """LIVE stage outputs [S-1, mb, S, d] -> wire [S-1, mb, S, c].

            Only ``out[:S-1]`` is encoded: the last stage's output would
            land in slot 0 and be overwritten by ``ingest`` — compressing
            that dead slot is pure waste (and would double-compress under
            the learned codecs)."""
            if comp == "int8":
                return jax.vmap(lambda x: codecs.int8_boundary(cfg, x))(
                    outs)
            if comp in codecs.LEARNED:       # boundary b uses w_c[b]
                return jax.vmap(
                    lambda p, x: codecs.encode_wire(cfg, comp, p, x))(
                        bparams, outs)
            return outs

        def decode(wire):
            """Wire [S, mb, S, c] -> stage inputs [S, mb, S, d].  Slot 0
            is dead (overwritten by ``ingest`` right after); slot ``s >=
            1`` decompresses boundary ``s-1`` with ``w_d[s-1]``."""
            if comp not in codecs.LEARNED:
                return wire                  # none/int8: wire is d-dim
            x = jax.vmap(lambda p, z: codecs.decode_wire(cfg, comp, p, z))(
                bparams, wire[1:])
            full = jnp.zeros(wire.shape[:-1] + (cfg.d_model,), wire.dtype)
            return full.at[1:].set(x)

        def ingest(t):
            """Embed the microbatch entering slot 0 at tick ``t``."""
            x = model_lib.embed(cfg, params, tok_mb[jnp.clip(t, 0, M - 1)],
                                batch_axes=("data",))
            return constrain(x, "data", None, None)

        def tick(carry, t):
            wire, aux_buf, ces, auxs = carry
            wire = constrain(wire, "pod", "data", None, None)
            x = decode(wire).at[0].set(ingest(t))
            x = constrain(x, "pod", "data", None, None)
            pos = (pos_mb if pos_axis is None
                   else pos_mb[jnp.clip(t - jnp.arange(S_), 0, M - 1)])
            out, aux_out = v_stage(stage_blocks, x, aux_buf, pos)
            # the final stage owns the head: no boundary crossing here
            idx = jnp.clip(t - (S_ - 1), 0, M - 1)
            logits = model_lib.head(cfg, params, out[-1],
                                    batch_axes=("data",))
            ces = ces.at[idx].set(steps_lib.cross_entropy(
                logits, lab_mb[idx]))
            auxs = auxs.at[idx].set(aux_out[-1])
            # warm-up ticks (t < S-1) write garbage into slot 0 of ces/auxs;
            # the true microbatch-0 write at t == S-1 overwrites it, and the
            # scatter's transpose zeroes the dead cotangents.
            #
            # Shift out[s] -> slot s+1 as a static-index update-slice (a
            # roll of the full buffer would drag the dead last-stage
            # output along for the ride).
            wire = jnp.zeros((S_, mb, S, wdim), out.dtype)
            wire = wire.at[1:].set(encode(out[:S_ - 1]))
            aux_buf = jnp.roll(aux_out, 1, 0).at[0].set(0.0)
            wire = constrain(wire, "pod", "data", None, None)
            return (wire, aux_buf, ces, auxs), None

        if do_remat:
            tick = jax.checkpoint(
                tick, policy=jax.checkpoint_policies.nothing_saveable)

        wire0 = jnp.zeros((S_, mb, S, wdim), cfg.compute_jdtype)
        carry0 = (wire0, jnp.zeros((S_,), jnp.float32),
                  jnp.zeros((M,), jnp.float32), jnp.zeros((M,), jnp.float32))
        (_, _, ces, auxs), _ = jax.lax.scan(
            tick, carry0, jnp.arange(M + S_ - 1))
        ce = ces.mean()
        return ce + auxs.mean(), ce

    def train_step(state: Tree, batch: Tree):
        params = state["params"]
        (loss, ce), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch)
        updates, opt = optimizer.update(grads, state["opt"], params)
        new_params = jax.tree.map(lambda p, u: p + u.astype(p.dtype),
                                  params, updates)
        return ({"params": new_params, "opt": opt,
                 "step": state["step"] + 1},
                {"loss": loss, "ce": ce})

    return train_step


def _plan_stage_blocks(cfg: ArchConfig, plan: StagePlan,
                       blocks: Tree) -> list[list[Tree]]:
    """Per-stage ``[tree-per-run]`` lists sliced from the global layer
    stacks — the non-periodic twin of :func:`_stage_blocks`.  Every
    plan run is a contiguous same-kind layer range, so it sits inside
    exactly one maximal global run: a static differentiable slice."""
    g_runs = model_lib.segments(cfg.block_kinds)
    starts = [0]
    for _, c in g_runs:
        starts.append(starts[-1] + c)
    per = cfg.n_layers // plan.n_stages
    out: list[list[Tree]] = []
    for s, spec in enumerate(plan.stages):
        off = s * per
        run_trees = []
        for _, c in spec.runs:
            ri = max(i for i in range(len(g_runs)) if starts[i] <= off)
            lo = off - starts[ri]
            run_trees.append(jax.tree.map(
                lambda a, _lo=lo, _c=c: a[_lo:_lo + _c], blocks[ri]))
            off += c
        out.append(run_trees)
    return out


def _make_whisper_reference_loss_fn(cfg: ArchConfig, n_stages: int,
                                    n_microbatches: int, comp: str):
    """Sequential staged whisper reference: encoder pod, then the
    decoder slice chain, with the tree-aware int8 boundary crossings the
    elastic path applies (boundary 0 quantizes the encoder output;
    interior boundaries quantize hidden + encoder state; token ids ride
    uncompressed).  ``batch["tokens"]`` is the composite
    ``{"audio", "tok"}`` payload the swarm feeds stage 0."""
    from repro.models import whisper as W
    from repro.train import steps as steps_lib   # lazy: steps imports models
    if comp in codecs.LEARNED:
        raise NotImplementedError(
            "learned boundary codecs are unsupported for encoder-decoder "
            "stacks (tree-valued boundaries)")
    M = n_microbatches
    per = cfg.n_layers // (n_stages - 1)

    def cross(x):
        return codecs.int8_boundary(cfg, x) if comp == "int8" else x

    def loss_fn(params: Tree, batch: Tree):
        audio, tok = batch["tokens"]["audio"], batch["tokens"]["tok"]
        labels = batch["labels"]
        B, S = tok.shape
        if B % M:
            raise ValueError(f"batch {B} not divisible by {M} microbatches")
        mb = B // M
        ces = []
        for m in range(M):
            au = audio.reshape(M, mb, *audio.shape[1:])[m]
            tk = tok.reshape(M, mb, S)[m]
            lab = labels.reshape(M, mb, S)[m]
            enc = cross(W.encode(cfg, params, au))        # boundary 0
            x = W.embed_tokens(cfg, params["embed"], tk)
            for s in range(1, n_stages):
                lo = (s - 1) * per
                blocks_s = jax.tree.map(
                    lambda a, _lo=lo: a[_lo:_lo + per],
                    params["dec_blocks"])
                x = W.dec_scan(cfg, blocks_s, x, enc, jnp.arange(S))
                if s < n_stages - 1:   # interior boundary: whole tree
                    x, enc = cross(x), cross(enc)
            logits = model_lib.head(cfg, params, x, batch_axes=("data",))
            ces.append(steps_lib.cross_entropy(logits, lab))
        ce = jnp.mean(jnp.stack(ces))
        return ce, ce

    return loss_fn


def make_reference_loss_fn(cfg: ArchConfig, n_stages: int,
                           n_microbatches: int, *,
                           compress: Optional[str] = None):
    """Sequential single-device twin of the pipelined loss: the SAME staged
    computation — per-microbatch stage chain with the identical boundary
    codec applied between consecutive stages — but with no vmap, no buffer
    shift and no bubble.  This is the equivalence oracle the codec tests
    compare :func:`make_pipeline_train_step` against (and the math the
    elastic path in ``repro.core`` executes peer-by-peer).

    Periodic stacks run the vmappable stage fn per stage (bit-identical
    to the historical behavior).  Non-periodic mixed-kind stacks and
    encoder-decoder stacks run their plan-driven stage chain — those
    have no GSPMD twin (``make_pipeline_train_step`` still requires
    periodicity) but serve as the elastic path's oracle."""
    try:
        plan = get_stage_plan(cfg, n_stages)
    except ValueError as e:
        raise ValueError(
            f"{cfg.name}: layer stack cannot split at {n_stages} stages "
            f"({e})") from e
    comp = _resolve_codec(cfg, n_stages, compress)
    if plan.is_encdec:
        return _make_whisper_reference_loss_fn(cfg, n_stages,
                                               n_microbatches, comp)
    periodic = plan.periodic
    stage_fn = _make_stage_fn(cfg, n_stages, remat=False) if periodic \
        else None
    M = n_microbatches

    from repro.train import steps as steps_lib   # lazy: steps imports models

    def loss_fn(params: Tree, batch: Tree):
        tokens, labels = batch["tokens"], batch["labels"]
        B, S = tokens.shape
        if B % M:
            raise ValueError(f"batch {B} not divisible by {M} microbatches")
        mb = B // M
        if periodic:
            stage_blocks = _stage_blocks(cfg, params["blocks"], n_stages)
        else:
            plan_blocks = _plan_stage_blocks(cfg, plan, params["blocks"])
            cores = [make_block_core(cfg, list(spec.runs), spec.reps)
                     for spec in plan.stages]
        bparams = (_boundary_params(params, comp, n_stages)
                   if comp in codecs.LEARNED else None)
        ces, auxs = [], []
        for m in range(M):
            tok = tokens.reshape(M, mb, S)[m]
            lab = labels.reshape(M, mb, S)[m]
            if "positions" in batch:                   # mrope: [3, B, S]
                p = batch["positions"]
                pos = p.reshape(p.shape[0], M, mb, S)[:, m]
            else:
                pos = model_lib.default_positions(cfg, mb, S)
            x = model_lib.embed(cfg, params, tok, batch_axes=("data",))
            aux = jnp.zeros((), jnp.float32)
            for s in range(n_stages):
                if periodic:
                    blocks_s = [jax.tree.map(lambda a: a[s], t)
                                for t in stage_blocks]
                    x, aux = stage_fn(blocks_s, x, aux, pos)
                else:
                    x, aux = cores[s](plan_blocks[s], x, aux, pos)
                if s < n_stages - 1:
                    x = boundary_crossing(cfg, comp, bparams, s, x)
            logits = model_lib.head(cfg, params, x, batch_axes=("data",))
            ces.append(steps_lib.cross_entropy(logits, lab))
            auxs.append(aux)
        ce = jnp.mean(jnp.stack(ces))
        return ce + jnp.mean(jnp.stack(auxs)), ce

    return loss_fn
