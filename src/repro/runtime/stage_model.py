"""Partition an ArchConfig into SWARM pipeline stages (stage programs).

Stage 0 additionally owns the embedding, the last stage the final norm +
LM head + loss (mirroring the paper's §4.3 placement).  Backward runs via
activation checkpointing: a stage recomputes its forward from the boundary
input it is handed, so backward can be re-routed to *any* peer of the stage
after a failure (App. A).

Under a learned boundary codec (paper App. J: ``compress="bottleneck"`` /
``"maxout"``) each stage's program *includes* its side of the codec: a
sending stage compresses its output (owning ``w_c`` for the bottleneck), a
receiving stage decompresses its input (owning ``w_d``) — so the tensor a
trainer carries between peers IS the c-dim wire tensor, and codec gradients
arrive through the ordinary per-stage ``bwd`` like any other parameter.
``"int8"`` stays outside the programs (the trainer round-trips the wire
tensor), matching SWARM's quantize-on-send.

The builders are *span-parameterized*: :func:`build_stage_programs` is the
``[s, s+1)`` special case of the same machinery
:func:`build_span_program` uses to fuse a contiguous span ``[lo, hi)`` of
stages into ONE jitted fwd/bwd (the
:class:`repro.runtime.pipeline.PipelineExecutor` backend).  Inside a span,
intra-span boundaries never leave the device: chaining stage ``b``'s
in-program compress with stage ``b+1``'s decompress reproduces the exact
single-stage math, minus the host crossing.  Structurally identical
consecutive stages are stacked along a leading stage dim (the layout
the GSPMD shifting buffer vmaps over ``pod``) and scanned over it;
the per-stage layer math itself is
:func:`repro.dist.pipeline.make_block_core`, shared with the compiled
pipeline, so span peers, single-stage peers, and the GSPMD step compute
one set of stage numerics.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.compression import codecs
from repro.dist.constrain import constrain
from repro.dist.pipeline import make_block_core
from repro.models.config import ArchConfig
from repro.models.stage_plan import StagePlan, get_stage_plan
from repro.models import params as P
from repro.models import layers as L
from repro.models import model as model_lib
from repro.models import flops as F

Tree = Any


@dataclasses.dataclass
class StageProgram:
    stage: int
    n_stages: int
    specs: Tree
    fwd: Callable                 # jitted
    bwd: Callable                 # jitted
    fwd_flops_per_token: float
    # includes checkpoint recompute, and the forward run again wherever
    # ``bwd`` runs: every stage but a last stage whose backward consumes
    # the residuals ``fwd_save`` kept (``bwd_saved``)
    bwd_flops_per_token: float
    fwd_fn: Optional[Callable] = None   # unjitted (mesh backends re-jit
    bwd_fn: Optional[Callable] = None   # with their own shardings)
    # decoder-only last stage only (jitted), else None:
    # fwd_save(params, inp, labels) -> (loss, saved) keeps the pullback's
    # residuals; bwd_saved(params, inp, labels, saved) -> (gx, gp)
    # consumes them (donated) instead of running the forward again
    fwd_save: Optional[Callable] = None
    bwd_saved: Optional[Callable] = None


@dataclasses.dataclass
class SpanProgram:
    """A contiguous span ``[lo, hi)`` of stages fused into one jitted step.

    ``fwd``/``bwd`` take a *tuple* of per-stage param trees (ordered
    ``lo..hi-1``, each shaped exactly like the corresponding
    :class:`StageProgram`'s ``specs``) so a span peer's state stays
    per-stage-keyed: checkpoint cuts, peer-to-peer downloads and span
    split/merge hand-offs move single-stage snapshots, never a fused
    blob.  ``bwd`` returns per-stage gradients as the same tuple.
    """
    span: tuple[int, int]
    n_stages: int
    specs: dict[int, Tree]        # per covered stage, keyed by global id
    fwd: Callable                 # jitted
    bwd: Callable                 # jitted
    fwd_flops_per_token: float    # whole-span totals
    bwd_flops_per_token: float
    fwd_fn: Optional[Callable] = None
    bwd_fn: Optional[Callable] = None

    @property
    def stages(self) -> range:
        return range(*self.span)


def _traced(fn: Callable, hook: Optional[Callable], stage, kind: str,
            donate_argnums: tuple = ()) -> Callable:
    """Jit ``fn``; if ``hook`` is given, call it once per XLA trace (the
    body side effect runs at trace time only) with the argument shapes —
    the runtime layer's retrace counter hangs off this.  ``stage`` is an
    int for single-stage programs, a ``(lo, hi)`` span tuple for spans.
    The program is named ``stage_<kind>`` or ``span_<kind>``
    (``jit_stage_bwd`` in a profiler trace)."""
    def counted(*args):
        if hook is not None:
            hook(stage, kind, tuple(tuple(a.shape) for a in args
                                    if hasattr(a, "shape")))
        return fn(*args)
    counted.__name__ = counted.__qualname__ = (
        ("span_" if isinstance(stage, tuple) else "stage_") + kind)
    return jax.jit(counted, donate_argnums=donate_argnums)


def _stage_runs(cfg: ArchConfig, s: int, n_stages: int):
    """(kinds, [per-run (kind, count)], reps) for one stage — read off
    the canonical :class:`~repro.models.stage_plan.StagePlan` instead of
    re-deriving it from ``cfg.block_kinds`` index math."""
    spec = get_stage_plan(cfg, n_stages).stages[s]
    return spec.kinds, list(spec.runs), spec.reps


def _cast_like(dy: Tree, y: Tree) -> Tree:
    """Cast a boundary cotangent tree to the forward output's dtypes
    (leaf-wise — whisper boundaries are trees, LM boundaries a tensor)."""
    return jax.tree.map(lambda t, yy: t.astype(yy.dtype), dy, y)


# whisper boundary payloads are trees; these keys are integer leaves
# (token ids) that ride the wire but never take gradients — stage fns
# split them out so every vjp runs over floating inputs only.
_INT_KEYS = ("tok",)


def _split_payload(inp: Tree) -> tuple[Tree, Tree]:
    floats = {k: v for k, v in inp.items() if k not in _INT_KEYS}
    ints = {k: v for k, v in inp.items() if k in _INT_KEYS}
    return floats, ints


def _stage_specs(cfg: ArchConfig, s: int, n_stages: int, comp: str,
                 learned: bool) -> Tree:
    """One stage's ParamSpec tree: blocks + edge extras (embed / head) +
    its side(s) of the learned boundary codec."""
    _, runs, _ = _stage_runs(cfg, s, n_stages)
    from repro.models.blocks import REGISTRY
    specs: Tree = {"blocks": [
        model_lib.stack_specs(REGISTRY[k][0](cfg), n) for k, n in runs]}
    if s == 0:
        specs["embed"] = P.ParamSpec(
            (cfg.vocab_size, cfg.d_model), cfg.param_jdtype, "embed",
            ("vocab", "embed"))
    if s == n_stages - 1:
        specs["final_norm"] = L.norm_specs(cfg)
        if not cfg.tie_embeddings or s != 0:
            specs["head"] = P.ParamSpec(
                (cfg.d_model, cfg.vocab_size), cfg.param_jdtype,
                "normal", ("embed", "vocab"))
    if learned:
        # receiving side (w_d) for s > 0, sending side (w_c) for
        # s < S-1; maxout's compress is param-free so its stage-0
        # "boundary" tree is empty and omitted
        bnd: Tree = {}
        if s > 0:
            bnd.update(codecs.receiver_specs(cfg, comp))
        if s < n_stages - 1:
            bnd.update(codecs.sender_specs(cfg, comp))
        if bnd:
            specs["boundary"] = bnd
    return specs


def _make_stage_fwd(cfg: ArchConfig, s: int, n_stages: int, comp: str,
                    learned: bool) -> Callable:
    """Stage ``s``'s wire-to-wire forward: decode the inbound wire tensor
    (embed for stage 0), run the stage's layers through the shared block
    core, emit the outbound wire tensor (hidden for the last stage — the
    head/loss is applied by the caller)."""
    _, runs, reps = _stage_runs(cfg, s, n_stages)
    # per-layer remat: a full-width stage's saved activations (11.3 GB
    # of backward temporaries for a swarm-1b stage at 4 x 1024 tokens)
    # would not fit one 16 GB chip beside its params and Adam state
    core = make_block_core(cfg, runs, reps, remat=True)
    is_first, is_last = s == 0, s == n_stages - 1

    def stage_fwd(params: Tree, inp):
        if is_first:
            tokens = inp
            x = params["embed"][tokens].astype(cfg.compute_jdtype)
            if cfg.scale_embed:
                x = x * (cfg.d_model ** 0.5)
        else:
            x = inp.astype(cfg.compute_jdtype)
            if learned:          # wire tensor arrives c-dim: restore
                x = codecs.decode_wire(cfg, comp,
                                       params.get("boundary"), x)
        positions = jnp.arange(x.shape[1])
        x, _aux = core(params["blocks"], x,
                       jnp.zeros((), jnp.float32), positions)
        if learned and not is_last:    # emit the c-dim wire tensor
            # (fused encode + wire QDQ under cfg.kernels / cfg.wire_quant)
            x = codecs.encode_wire(cfg, comp, params.get("boundary"), x)
        return x

    return stage_fwd


def _head_logits(cfg: ArchConfig, params: Tree, x):
    """Final norm + LM head — the last stage's extra ownership.  Shared
    by the training loss below and the serving session programs
    (``repro.serve.programs``), so staged decode and staged training
    read logits through one code path."""
    x = L.apply_norm(cfg, params["final_norm"], x)
    w = (params["embed"].T if cfg.tie_embeddings and "head" not in
         params else params["head"])
    logits = x @ w.astype(x.dtype)
    return logits.astype(jnp.float32)


def _head_loss(cfg: ArchConfig, params: Tree, x, labels):
    """Logits + token-sum CE (so microbatch gradients add exactly,
    App. E)."""
    logits = _head_logits(cfg, params, x)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None],
                               axis=-1)[..., 0]
    return jnp.sum(lse - gold)


def _saved_pair(loss_fn: Callable, with_gx: bool
                ) -> tuple[Callable, Callable]:
    """The last stage's loss forward that keeps its pullback, and the
    backward that consumes it (``StageProgram.fwd_save``/``bwd_saved``).

    ``fwd_save`` takes ``jax.vjp`` of ``loss_fn`` with respect to the
    params (and the boundary input when ``with_gx``) and returns the loss
    and the pullback's residual leaves, minus those that are the
    program's own arguments: returned from the program, an argument
    would come back as a fresh copy (the stage's float32 params, on
    every microbatch).  Each trace records, per argument shapes, the
    pullback's treedef and where the argument leaves sat in it;
    ``bwd_saved`` puts the arguments it is given back in those places
    and applies the pullback to a cotangent of 1, for the gradients
    ``jax.grad`` of ``loss_fn`` gives."""
    layouts: dict = {}

    def key(*args):
        return tuple((a.shape, a.dtype) for a in jax.tree.leaves(args))

    def fwd_save(params, inp, labels):
        if with_gx:
            loss, pullback = jax.vjp(lambda p, x: loss_fn(p, x, labels),
                                     params, inp)
        else:
            loss, pullback = jax.vjp(lambda p: loss_fn(p, inp, labels),
                                     params)
        leaves, treedef = jax.tree.flatten(pullback)
        args = {id(a): i for i, a in
                enumerate(jax.tree.leaves((params, inp, labels)))}
        at = {j: args[id(r)] for j, r in enumerate(leaves) if id(r) in args}
        layouts[key(params, inp, labels)] = (treedef, at, loss.shape,
                                             loss.dtype)
        return loss, [r for j, r in enumerate(leaves) if j not in at]

    def bwd_saved(params, inp, labels, saved):
        treedef, at, shape, dtype = layouts[key(params, inp, labels)]
        args = jax.tree.leaves((params, inp, labels))
        rest = iter(saved)
        pullback = jax.tree.unflatten(treedef, [
            args[at[j]] if j in at else next(rest)
            for j in range(treedef.num_leaves)])
        grads = pullback(jnp.ones(shape, dtype))
        if with_gx:
            gp, gx = grads
            return gx, gp
        (gp,) = grads
        return None, gp

    return fwd_save, bwd_saved


def _stage_fwd_flops(cfg: ArchConfig, s: int, n_stages: int, seq_len: int,
                     comp: str, learned: bool) -> float:
    is_first, is_last = s == 0, s == n_stages - 1
    codec_f = codecs.codec_flops_per_token(
        cfg, comp, sender=learned and not is_last,
        receiver=learned and not is_first)
    return get_stage_plan(cfg, n_stages).stage_flops(s, seq_len) + codec_f


# --------------------------------------------------- encoder-decoder stages
def _stage_specs_encdec(cfg: ArchConfig, s: int, n_stages: int) -> Tree:
    """Whisper stage specs: stage 0 is the encoder pod, stages
    ``1..n_stages-1`` split the decoder; stage 1 owns the token embed,
    the last stage the final norm + head (plan ownership)."""
    from repro.models import whisper as W
    if s == 0:
        return {"enc_blocks": model_lib.stack_specs(
                    W.enc_block_specs(cfg), cfg.encoder_layers),
                "enc_norm": L.norm_specs(cfg)}
    per = cfg.n_layers // (n_stages - 1)
    specs: Tree = {"dec_blocks": model_lib.stack_specs(
        W.dec_block_specs(cfg), per)}
    if s == 1:
        specs["embed"] = P.ParamSpec(
            (cfg.vocab_size, cfg.d_model), cfg.param_jdtype, "embed",
            ("vocab", "embed"))
    if s == n_stages - 1:
        specs["final_norm"] = L.norm_specs(cfg)
        specs["head"] = P.ParamSpec(
            (cfg.d_model, cfg.vocab_size), cfg.param_jdtype, "normal",
            ("embed", "vocab"))
    return specs


def _make_stage_core_encdec(cfg: ArchConfig, s: int, n_stages: int
                            ) -> Callable:
    """Stage ``s``'s float-to-float core: ``(params, floats, ints) ->
    out_floats``.  Integer token ids ride the boundary tree untouched
    (the wrappers below pass them around every vjp), so cross-attention
    gradients flow stage-to-stage through purely floating cotangent
    trees: boundary 0 ships ``{"enc"}``, interior boundaries
    ``{"x", "enc"}`` — the encoder pod hand-off sits exactly at the
    cross-attention boundary."""
    from repro.models import whisper as W
    is_enc, first_dec = s == 0, s == 1
    is_last = s == n_stages - 1

    def core(params: Tree, floats: Tree, ints: Tree) -> Tree:
        if is_enc:
            return {"enc": W.encode(cfg, params, floats["audio"])}
        enc = floats["enc"].astype(cfg.compute_jdtype)
        if first_dec:
            x = W.embed_tokens(cfg, params["embed"], ints["tok"])
        else:
            x = floats["x"].astype(cfg.compute_jdtype)
        x = W.dec_scan(cfg, params["dec_blocks"], x, enc,
                       jnp.arange(x.shape[1]))
        return {"x": x} if is_last else {"x": x, "enc": enc}

    return core


def _build_stage_programs_encdec(cfg: ArchConfig, n_stages: int,
                                 seq_len: int,
                                 trace_hook: Optional[Callable]
                                 ) -> list[StageProgram]:
    programs = []
    for s in range(n_stages):
        specs = _stage_specs_encdec(cfg, s, n_stages)
        core = _make_stage_core_encdec(cfg, s, n_stages)
        is_enc, is_last = s == 0, s == n_stages - 1

        if is_last:
            def fwd(params, inp, labels, _c=core):
                floats, ints = _split_payload(inp)
                return _head_loss(cfg, params,
                                  _c(params, floats, ints)["x"], labels)

            def bwd(params, inp, labels, _c=core):
                floats, ints = _split_payload(inp)

                def sl(p, f):
                    return _head_loss(cfg, p, _c(p, f, ints)["x"], labels)
                loss, (gp, gf) = jax.value_and_grad(sl, argnums=(0, 1))(
                    params, floats)
                return loss, gf, gp
        elif is_enc:
            def fwd(params, inp, _c=core):
                floats, ints = _split_payload(inp)
                return {**_c(params, floats, ints), **ints}

            def bwd(params, inp, dy, _c=core):
                floats, ints = _split_payload(inp)
                dy_f, _ = _split_payload(dy)
                y, pullback = jax.vjp(lambda p: _c(p, floats, ints), params)
                (gp,) = pullback(_cast_like(dy_f, y))
                return None, gp
        else:
            def fwd(params, inp, _c=core):
                floats, ints = _split_payload(inp)
                return {**_c(params, floats, ints), **ints}

            def bwd(params, inp, dy, _c=core):
                floats, ints = _split_payload(inp)
                dy_f, _ = _split_payload(dy)
                y, pullback = jax.vjp(
                    lambda p, f: _c(p, f, ints), params, floats)
                gp, gf = pullback(_cast_like(dy_f, y))
                return gf, gp

        fwd_f = _stage_fwd_flops(cfg, s, n_stages, seq_len, "none", False)
        programs.append(StageProgram(
            stage=s, n_stages=n_stages, specs=specs,
            fwd=_traced(fwd, trace_hook, s, "fwd"),
            bwd=_traced(bwd, trace_hook, s, "bwd"),
            fwd_flops_per_token=fwd_f, bwd_flops_per_token=3.0 * fwd_f,
            fwd_fn=fwd, bwd_fn=bwd))
    return programs


def _build_span_encdec(cfg: ArchConfig, n_stages: int, seq_len: int,
                       span: tuple[int, int],
                       trace_hook: Optional[Callable]) -> SpanProgram:
    lo, hi = span
    covers_last = hi == n_stages
    plan = get_stage_plan(cfg, n_stages)
    specs = {s: _stage_specs_encdec(cfg, s, n_stages)
             for s in range(lo, hi)}
    cores = {s: _make_stage_core_encdec(cfg, s, n_stages)
             for s in range(lo, hi)}
    fwd_f = sum(_stage_fwd_flops(cfg, s, n_stages, seq_len, "none", False)
                for s in range(lo, hi))
    # plan-driven fusion: contiguous structurally identical decoder
    # stages scan as one group; the encoder/embed/head stages hand off
    # sequentially at their kind boundaries
    groups = [(s0 - lo, c) for s0, c in plan.fusion_groups(span)]

    def span_core(ps, floats, ints):
        cur = floats
        for start, count in groups:
            f = cores[lo + start]
            if count >= 2:
                members = [ps[i] for i in range(start, start + count)]
                stacked = jax.tree.map(
                    lambda *xs: jnp.stack(xs), *members)
                stacked = jax.tree.map(
                    lambda a: constrain(a, "pod", *([None] * (a.ndim - 1))),
                    stacked)

                def body(c, p_s, _f=f):
                    return _f(p_s, c, ints), None
                cur, _ = jax.lax.scan(body, cur, stacked)
            else:
                cur = f(ps[start], cur, ints)
        return cur

    if covers_last:
        def span_loss(ps, floats, ints, labels):
            return _head_loss(cfg, ps[-1],
                              span_core(ps, floats, ints)["x"], labels)

        def fwd(ps, inp, labels):
            floats, ints = _split_payload(inp)
            return span_loss(ps, floats, ints, labels)

        if lo == 0:
            def bwd(ps, inp, labels):
                floats, ints = _split_payload(inp)
                loss, gp = jax.value_and_grad(span_loss)(
                    ps, floats, ints, labels)
                return loss, None, gp
        else:
            def bwd(ps, inp, labels):
                floats, ints = _split_payload(inp)
                loss, (gp, gf) = jax.value_and_grad(
                    span_loss, argnums=(0, 1))(ps, floats, ints, labels)
                return loss, gf, gp
    else:
        def fwd(ps, inp):
            floats, ints = _split_payload(inp)
            return {**span_core(ps, floats, ints), **ints}

        if lo == 0:
            def bwd(ps, inp, dy):
                floats, ints = _split_payload(inp)
                dy_f, _ = _split_payload(dy)
                y, pullback = jax.vjp(
                    lambda p: span_core(p, floats, ints), ps)
                (gp,) = pullback(_cast_like(dy_f, y))
                return None, gp
        else:
            def bwd(ps, inp, dy):
                floats, ints = _split_payload(inp)
                dy_f, _ = _split_payload(dy)
                y, pullback = jax.vjp(
                    lambda p, f: span_core(p, f, ints), ps, floats)
                gp, gf = pullback(_cast_like(dy_f, y))
                return gf, gp

    return SpanProgram(
        span=(lo, hi), n_stages=n_stages, specs=specs,
        fwd=_traced(fwd, trace_hook, (lo, hi), "fwd"),
        bwd=_traced(bwd, trace_hook, (lo, hi), "bwd"),
        fwd_flops_per_token=fwd_f, bwd_flops_per_token=3.0 * fwd_f,
        fwd_fn=fwd, bwd_fn=bwd)


def build_stage_programs(cfg: ArchConfig, n_stages: int, seq_len: int,
                         compress: Optional[str] = None,
                         trace_hook: Optional[Callable] = None
                         ) -> list[StageProgram]:
    get_stage_plan(cfg, n_stages)      # validates the split (ValueError)
    comp = codecs.resolve_mode(cfg, compress)
    learned = comp in codecs.LEARNED and n_stages > 1
    if cfg.encoder_layers:
        if learned:
            raise NotImplementedError(
                "learned boundary codecs are unsupported for "
                "encoder-decoder stage programs (tree-valued boundaries)")
        return _build_stage_programs_encdec(cfg, n_stages, seq_len,
                                            trace_hook)
    programs = []
    for s in range(n_stages):
        specs = _stage_specs(cfg, s, n_stages, comp, learned)
        stage_fwd = _make_stage_fwd(cfg, s, n_stages, comp, learned)
        is_first, is_last = s == 0, s == n_stages - 1

        def stage_loss(params, inp, labels, _fwd=stage_fwd):
            return _head_loss(cfg, params, _fwd(params, inp), labels)

        if is_last:
            def fwd(params, inp, labels, _sl=stage_loss):
                return _sl(params, inp, labels)

            def bwd(params, inp, labels, _sl=stage_loss):
                if is_first_and_last := (n_stages == 1):
                    (loss), g = jax.value_and_grad(_sl)(params, inp, labels)
                    return loss, None, g
                (loss), (gp, gx) = jax.value_and_grad(_sl, argnums=(0, 1))(
                    params, inp, labels)
                return loss, gx, gp
        elif is_first:
            def fwd(params, inp, _sf=stage_fwd):
                return _sf(params, inp)

            def bwd(params, inp, dy, _sf=stage_fwd):
                y, pullback = jax.vjp(lambda p: _sf(p, inp), params)
                (gp,) = pullback(dy.astype(y.dtype))
                return None, gp
        else:
            def fwd(params, inp, _sf=stage_fwd):
                return _sf(params, inp)

            def bwd(params, inp, dy, _sf=stage_fwd):
                y, pullback = jax.vjp(_sf, params, inp)
                gp, gx = pullback(dy.astype(y.dtype))
                return gx, gp
        fwd_j = _traced(fwd, trace_hook, s, "fwd")
        bwd_j = _traced(bwd, trace_hook, s, "bwd")
        pair = {}
        if is_last:
            # the saved pair runs the head again in its backward: kept,
            # its f32 logits (tokens x vocab) and bf16 weight cast would
            # hold device memory from the forward to the backward's end
            def saved_loss(params, inp, labels, _fwd=stage_fwd):
                return jax.checkpoint(functools.partial(_head_loss, cfg))(
                    params, _fwd(params, inp), labels)

            fwd_save, bwd_saved = _saved_pair(saved_loss, not is_first)
            pair = dict(
                fwd_save=_traced(fwd_save, trace_hook, s, "fwd_save"),
                bwd_saved=_traced(bwd_saved, trace_hook, s, "bwd_saved",
                                  donate_argnums=(3,)))

        fwd_f = _stage_fwd_flops(cfg, s, n_stages, seq_len, comp, learned)
        programs.append(StageProgram(
            stage=s, n_stages=n_stages, specs=specs, fwd=fwd_j, bwd=bwd_j,
            fwd_flops_per_token=fwd_f,
            bwd_flops_per_token=3.0 * fwd_f,   # recompute + 2x backward
            fwd_fn=fwd, bwd_fn=bwd, **pair,
        ))
    return programs


# ------------------------------------------------------------- span fusion
def _span_fingerprint(cfg: ArchConfig, s: int, n_stages: int, comp: str,
                      learned: bool, specs_s: Tree):
    """Two covered stages may share one scan slot iff this matches: same
    plan structure (runs/reps/edge ownership) and bit-identical
    param-tree geometry."""
    spec = get_stage_plan(cfg, n_stages).stages[s]
    leaves, treedef = jax.tree.flatten(specs_s, is_leaf=P.is_spec)
    return spec.structural_key + (treedef, tuple(leaves))


def _scan_groups(fingerprints: list) -> list[tuple[int, int]]:
    """Maximal runs of consecutive equal fingerprints, as (start, count)
    over span-local indices."""
    groups, i = [], 0
    while i < len(fingerprints):
        j = i + 1
        while j < len(fingerprints) and fingerprints[j] == fingerprints[i]:
            j += 1
        groups.append((i, j - i))
        i = j
    return groups


def build_span_program(cfg: ArchConfig, n_stages: int, seq_len: int,
                       span: tuple[int, int],
                       compress: Optional[str] = None,
                       trace_hook: Optional[Callable] = None
                       ) -> SpanProgram:
    """Fuse stages ``[lo, hi)`` into one jitted fwd/bwd.

    The single-jit span step is what lets a well-provisioned peer hold
    *more of the model* (the paper's square-cube rebalancing; Varuna's
    stage fusion): intra-span boundaries stay on-device — under a learned
    codec the sending stage's in-program compress chains into the
    receiving stage's decompress, reproducing the single-stage math
    exactly, with zero host bytes for the fused boundary.  Runs of
    structurally identical covered stages are stacked along a leading
    stage dim (constrained to ``pod`` when a mesh is ambient — the same
    sharded stacking the GSPMD tick uses) and executed as a ``lax.scan``
    over stages.
    """
    lo, hi = span
    if not (0 <= lo < hi <= n_stages):
        raise ValueError(f"span [{lo}, {hi}) outside [0, {n_stages})")
    get_stage_plan(cfg, n_stages)      # validates the split (ValueError)
    comp = codecs.resolve_mode(cfg, compress)
    learned = comp in codecs.LEARNED and n_stages > 1
    if cfg.encoder_layers:
        if learned:
            raise NotImplementedError(
                "learned boundary codecs are unsupported for "
                "encoder-decoder span programs (tree-valued boundaries)")
        return _build_span_encdec(cfg, n_stages, seq_len, span, trace_hook)
    covers_last = hi == n_stages

    specs: dict[int, Tree] = {}
    fwds: dict[int, Callable] = {}
    fprints = []
    fwd_f = 0.0
    for s in range(lo, hi):
        specs[s] = _stage_specs(cfg, s, n_stages, comp, learned)
        fwds[s] = _make_stage_fwd(cfg, s, n_stages, comp, learned)
        fprints.append(_span_fingerprint(cfg, s, n_stages, comp, learned,
                                         specs[s]))
        fwd_f += _stage_fwd_flops(cfg, s, n_stages, seq_len, comp, learned)
    groups = _scan_groups(fprints)

    def span_fwd(params_by_stage, inp):
        """(tuple ordered lo..hi-1, inbound wire) -> hidden (covers_last)
        or outbound wire tensor."""
        x = inp
        for start, count in groups:
            f = fwds[lo + start]
            if count >= 2:
                members = [params_by_stage[i]
                           for i in range(start, start + count)]
                stacked = jax.tree.map(
                    lambda *xs: jnp.stack(xs), *members)
                stacked = jax.tree.map(
                    lambda a: constrain(a, "pod", *([None] * (a.ndim - 1))),
                    stacked)

                def body(x, p_s, _f=f):
                    return _f(p_s, x), None
                x, _ = jax.lax.scan(body, x, stacked)
            else:
                x = f(params_by_stage[start], x)
        return x

    if covers_last:
        def span_loss(ps, inp, labels, _sf=span_fwd):
            return _head_loss(cfg, ps[-1], _sf(ps, inp), labels)

        def fwd(ps, inp, labels, _sl=span_loss):
            return _sl(ps, inp, labels)

        if lo == 0:
            def bwd(ps, inp, labels, _sl=span_loss):
                loss, gp = jax.value_and_grad(_sl)(ps, inp, labels)
                return loss, None, gp
        else:
            def bwd(ps, inp, labels, _sl=span_loss):
                loss, (gp, gx) = jax.value_and_grad(_sl, argnums=(0, 1))(
                    ps, inp, labels)
                return loss, gx, gp
    else:
        def fwd(ps, inp, _sf=span_fwd):
            return _sf(ps, inp)

        if lo == 0:
            def bwd(ps, inp, dy, _sf=span_fwd):
                y, pullback = jax.vjp(lambda p: _sf(p, inp), ps)
                (gp,) = pullback(dy.astype(y.dtype))
                return None, gp
        else:
            def bwd(ps, inp, dy, _sf=span_fwd):
                y, pullback = jax.vjp(_sf, ps, inp)
                gp, gx = pullback(dy.astype(y.dtype))
                return gx, gp

    return SpanProgram(
        span=(lo, hi), n_stages=n_stages, specs=specs,
        fwd=_traced(fwd, trace_hook, (lo, hi), "fwd"),
        bwd=_traced(bwd, trace_hook, (lo, hi), "bwd"),
        fwd_flops_per_token=fwd_f, bwd_flops_per_token=3.0 * fwd_f,
        fwd_fn=fwd, bwd_fn=bwd)


def init_stage_params(programs: list[StageProgram], key: jax.Array
                      ) -> list[Tree]:
    keys = jax.random.split(key, len(programs))
    return [P.init(k, p.specs) for k, p in zip(keys, programs)]


def split_whisper_params(cfg: ArchConfig, n_stages: int,
                         params: Tree) -> list[Tree]:
    """Slice a full whisper tree (``models.whisper.whisper_specs``
    layout) into per-stage trees shaped like the enc-dec stage programs
    — exact (every leaf a copy or slice), so the staged pipeline matches
    ``whisper_apply`` bit-for-bit."""
    per = cfg.n_layers // (n_stages - 1)
    out: list[Tree] = [{"enc_blocks": params["enc_blocks"],
                        "enc_norm": params["enc_norm"]}]
    for s in range(1, n_stages):
        lo = (s - 1) * per
        st: Tree = {"dec_blocks": jax.tree.map(
            lambda a, _lo=lo: a[_lo:_lo + per], params["dec_blocks"])}
        if s == 1:
            st["embed"] = params["embed"]
        if s == n_stages - 1:
            st["final_norm"] = params["final_norm"]
            st["head"] = params["head"]
        out.append(st)
    return out


def split_lm_params(cfg: ArchConfig, n_stages: int, params: Tree,
                    compress: Optional[str] = None) -> list[Tree]:
    """Slice a full-model param tree (``repro.models.model.lm_specs``
    layout) into per-stage trees shaped like :func:`_stage_specs` — how
    weights trained or loaded through the single-process path get served
    by a staged swarm.  Exact: every leaf is a copy or a slice of the
    original, so staged forward/decode matches the full model
    bit-for-bit (the serving equivalence test relies on this).

    Learned boundary codecs are unsupported: the single-process tree
    carries the GSPMD pipeline's per-boundary codec stack, not the
    per-stage ``w_c``/``w_d`` split the stage programs own.
    """
    comp = codecs.resolve_mode(cfg, compress)
    if comp in codecs.LEARNED and n_stages > 1:
        raise NotImplementedError(
            "split_lm_params cannot split learned boundary-codec params; "
            "init per-stage codec weights via init_stage_params instead")
    assert cfg.n_layers % n_stages == 0
    per = cfg.n_layers // n_stages
    if not cfg.share_groups:
        per_layer: list[Tree] = []
        for (kind, n), seg in zip(model_lib.segments(cfg.block_kinds),
                                  params["blocks"]):
            for i in range(n):
                per_layer.append(jax.tree.map(lambda a, _i=i: a[_i], seg))
    out: list[Tree] = []
    for s in range(n_stages):
        if cfg.share_groups:
            # one shared group per stage (stage s applies group s
            # `per` times) — slice keeps the leading stack dim of 1
            blocks = [jax.tree.map(lambda a, _s=s: a[_s:_s + 1],
                                   params["blocks"][0])]
        else:
            blocks, idx = [], s * per
            for kind, n in model_lib.segments(
                    cfg.block_kinds[s * per:(s + 1) * per]):
                trees = per_layer[idx:idx + n]
                idx += n
                blocks.append(jax.tree.map(
                    lambda *xs: jnp.stack(xs), *trees))
        st: Tree = {"blocks": blocks}
        if s == 0:
            st["embed"] = params["embed"]
        if s == n_stages - 1:
            st["final_norm"] = params["final_norm"]
            if not cfg.tie_embeddings:
                st["head"] = params["head"]
            elif s != 0:
                # tied embeddings with the embed table on another stage:
                # the last stage materializes the tied head
                st["head"] = params["embed"].T
        out.append(st)
    return out
