"""Session programs: span-parameterized prefill/decode for swarm serving.

Training's unit of work is a microbatch crossing the pipeline once; a
serving *session* crosses it once per generated token, carrying a decode
cache per covered stage between crossings.  A :class:`SessionProgram` is
the serving analogue of :class:`repro.runtime.stage_model.SpanProgram`:
stages ``[lo, hi)`` fused into one jitted ``prefill`` and one jitted
``decode``, parameterized the same way (tuple of per-stage param trees,
ordered ``lo..hi-1``) so the same per-stage-keyed
:class:`~repro.runtime.base.StageState` backs both — the KV caches live
in the state's ``"kv"`` keyed slot next to ``"grads"`` and ``"opt"``,
and ride the exact churn machinery (snapshot/restore, per-stage
hand-offs, ``export_slot``/``install_slot``) grads and opt already do.

Caches are allocated at ``total_len`` (the session's full horizon) by
the prefill, so decode steps write in place — no cache re-padding ever
happens between prefill and decode, which is what retired the
``decode_cache_specs`` shuffle from ``examples/serve_pipeline.py``.

Like the stage/span programs, session programs are cached process-wide
(one prefill + one decode compile per ``(config, span, horizon, codec)``
— N peers of a span share the jits) and report XLA traces to the same
``repro.runtime.numeric`` counters, tagged ``"serve"``.

:func:`full_session_program` wraps the single-process model path
(``repro.train.steps.make_prefill_step`` / ``make_serve_step``) in the
same interface — the token-for-token reference the staged swarm is
tested against, and what ``examples/serve_pipeline.py`` runs.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.compression import codecs
from repro.models.blocks import REGISTRY
from repro.models.config import ArchConfig
from repro.runtime import numeric as numeric_rt
from repro.runtime.stage_model import (_head_logits, _stage_fwd_flops,
                                       _stage_runs)

Tree = Any

# the StageState keyed slot serving KV caches live in (keyed by session)
KV_SLOT = "kv"


@dataclasses.dataclass
class SessionProgram:
    """Stages ``[lo, hi)`` fused into one prefill + one decode jit.

    ``prefill(params, inp) -> (out, kv)`` — ``inp`` is the token batch
    ``[B, S]`` when the span covers stage 0, the inbound wire tensor
    otherwise; ``out`` is the first generated token ``[B, 1]`` when the
    span covers the last stage, the *full-sequence* outbound wire tensor
    otherwise (a downstream span prefills from it).  ``kv`` is a tuple
    of per-covered-stage cache trees, allocated at ``total_len``.

    ``decode(params, kv, inp, pos) -> (out, kv)`` — one token step;
    ``inp`` is ``[B, 1]`` tokens or the one-position wire tensor, ``pos``
    the scalar write position (shared across the batch: continuous
    batching is slot-granular, sequences in one session advance in
    lockstep).
    """
    span: tuple[int, int]
    n_stages: int
    total_len: int
    prefill: Callable             # jitted
    decode: Callable              # jitted
    flops_per_token: float        # forward flops, summed over the span
    prefill_fn: Optional[Callable] = None
    decode_fn: Optional[Callable] = None

    @property
    def stages(self) -> range:
        return range(*self.span)

    @property
    def covers_first(self) -> bool:
        return self.span[0] == 0

    @property
    def covers_last(self) -> bool:
        return self.span[1] == self.n_stages


# (cfg, n_stages, (lo, hi), total_len, comp) -> SessionProgram; plus the
# full-model reference programs under (cfg, "full", total_len, remat)
_SESSIONS: dict[tuple, SessionProgram] = {}
_LOCK = threading.Lock()


def reset_session_cache() -> None:
    with _LOCK:
        _SESSIONS.clear()


def _embed_in(cfg: ArchConfig, params: Tree, tokens) -> jax.Array:
    x = params["embed"][tokens].astype(cfg.compute_jdtype)
    if cfg.scale_embed:
        x = x * (cfg.d_model ** 0.5)
    return x


def _make_stage_prefill(cfg: ArchConfig, s: int, n_stages: int,
                        comp: str, learned: bool) -> Callable:
    """Stage ``s``'s wire-to-wire prefill: same in/out framing as
    ``stage_model._make_stage_fwd`` (embed / codec at the edges), plus
    decode-cache emission at ``cache_len``."""
    _, runs, reps = _stage_runs(cfg, s, n_stages)
    is_first, is_last = s == 0, s == n_stages - 1

    def stage_prefill(params: Tree, inp, cache_len: int):
        if is_first:
            x = _embed_in(cfg, params, inp)
        else:
            x = inp.astype(cfg.compute_jdtype)
            if learned:
                x = codecs.decompress(cfg, comp, params.get("boundary"), x)
        positions = jnp.arange(x.shape[1])
        caches = []
        for (kind, _), seg_params in zip(runs, params["blocks"]):
            prefill_fn = REGISTRY[kind][4]

            def body(x, p_l, _pf=prefill_fn):
                y, _, cache = _pf(cfg, p_l, x, positions, cache_len)
                return y, cache

            if reps > 1:             # shared group applied `reps` times
                def group_body(x, p_g, _body=body):
                    cs = []
                    for _ in range(reps):
                        x, c = _body(x, p_g)
                        cs.append(c)
                    return x, jax.tree.map(lambda *a: jnp.stack(a), *cs)
                x, cs = jax.lax.scan(group_body, x, seg_params)
                cs = jax.tree.map(
                    lambda a: a.reshape(a.shape[0] * a.shape[1],
                                        *a.shape[2:]), cs)
            else:
                x, cs = jax.lax.scan(body, x, seg_params)
            caches.append(cs)
        if learned and not is_last:
            x = codecs.compress(cfg, comp, params.get("boundary"), x)
        return x, caches

    return stage_prefill


def _make_stage_decode(cfg: ArchConfig, s: int, n_stages: int,
                       comp: str, learned: bool) -> Callable:
    """Stage ``s``'s one-token decode against its caches (mirrors
    ``model.lm_decode_step``'s layer walk, wire-framed like the stage
    forward)."""
    _, runs, reps = _stage_runs(cfg, s, n_stages)
    is_first, is_last = s == 0, s == n_stages - 1

    def stage_decode(params: Tree, caches: Tree, inp, pos):
        if is_first:
            x = _embed_in(cfg, params, inp)
        else:
            x = inp.astype(cfg.compute_jdtype)
            if learned:
                x = codecs.decompress(cfg, comp, params.get("boundary"), x)
        B = x.shape[0]
        if cfg.rope == "mrope":
            positions = jnp.broadcast_to(pos, (3, B, 1))
        else:
            positions = jnp.broadcast_to(pos, (B, 1))
        new_caches = []
        for (kind, _), seg_params, seg_cache in zip(runs, params["blocks"],
                                                    caches):
            decode_fn = REGISTRY[kind][2]
            if reps > 1:
                def body(x, pc, _dec=decode_fn):
                    p_g, c_ls = pc      # group params + its [reps, ...] caches
                    def inner(x, c_l):
                        return _dec(cfg, p_g, x, c_l, pos, positions)
                    return jax.lax.scan(inner, x, c_ls)

                c_re = jax.tree.map(
                    lambda a: a.reshape(-1, reps, *a.shape[1:]), seg_cache)
                x, cs = jax.lax.scan(body, x, (seg_params, c_re))
                cs = jax.tree.map(
                    lambda a: a.reshape(a.shape[0] * reps, *a.shape[2:]),
                    cs)
            else:
                def body(x, pc, _dec=decode_fn):
                    p_l, c_l = pc
                    return _dec(cfg, p_l, x, c_l, pos, positions)
                x, cs = jax.lax.scan(body, x, (seg_params, seg_cache))
            new_caches.append(cs)
        if learned and not is_last:
            x = codecs.compress(cfg, comp, params.get("boundary"), x)
        return x, new_caches

    return stage_decode


def build_session_program(cfg: ArchConfig, n_stages: int,
                          span: tuple[int, int], total_len: int,
                          compress: Optional[str] = None,
                          trace_hook: Optional[Callable] = None
                          ) -> SessionProgram:
    lo, hi = span
    if not (0 <= lo < hi <= n_stages):
        raise ValueError(f"span [{lo}, {hi}) outside [0, {n_stages})")
    assert cfg.n_layers % n_stages == 0
    if cfg.family == "audio":
        raise NotImplementedError(
            "staged serving covers the LM families; audio serves through "
            "full_session_program")
    comp = codecs.resolve_mode(cfg, compress)
    learned = comp in codecs.LEARNED and n_stages > 1
    covers_last = hi == n_stages

    prefs = {s: _make_stage_prefill(cfg, s, n_stages, comp, learned)
             for s in range(lo, hi)}
    decs = {s: _make_stage_decode(cfg, s, n_stages, comp, learned)
            for s in range(lo, hi)}
    flops = sum(_stage_fwd_flops(cfg, s, n_stages, total_len, comp,
                                 learned) for s in range(lo, hi))

    def prefill_fn(params_by_stage, inp):
        x, kv = inp, []
        for i, s in enumerate(range(lo, hi)):
            x, caches = prefs[s](params_by_stage[i], x, total_len)
            kv.append(caches)
        kv = tuple(kv)
        if covers_last:
            logits = _head_logits(cfg, params_by_stage[-1], x[:, -1:])
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), kv
        return x, kv

    def decode_fn(params_by_stage, kv, inp, pos):
        x, new_kv = inp, []
        for i, s in enumerate(range(lo, hi)):
            x, caches = decs[s](params_by_stage[i], kv[i], x, pos)
            new_kv.append(caches)
        new_kv = tuple(new_kv)
        if covers_last:
            logits = _head_logits(cfg, params_by_stage[-1], x)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), new_kv
        return x, new_kv

    def traced(fn, kind):
        if trace_hook is None:
            return jax.jit(fn)

        def counted(*args):
            trace_hook((lo, hi), kind,
                       tuple(tuple(a.shape) for a in jax.tree.leaves(args)
                             if hasattr(a, "shape"))[:4])
            return fn(*args)
        counted.__name__ = counted.__qualname__ = kind
        return jax.jit(counted)

    return SessionProgram(
        span=(lo, hi), n_stages=n_stages, total_len=total_len,
        prefill=traced(prefill_fn, "prefill"),
        decode=traced(decode_fn, "decode"),
        flops_per_token=flops,
        prefill_fn=prefill_fn, decode_fn=decode_fn)


def get_session_program(cfg: ArchConfig, n_stages: int,
                        span: tuple[int, int], total_len: int,
                        compress: Optional[str] = None) -> SessionProgram:
    """The shared, counted session program for one span and horizon —
    one prefill/decode compile per ``(config, span, total_len, codec)``
    process-wide, same discipline as the stage/span program caches."""
    comp = codecs.resolve_mode(cfg, compress)
    key = (cfg, n_stages, tuple(span), total_len, comp)
    with _LOCK:
        prog = _SESSIONS.get(key)
    if prog is not None:
        return prog
    tag = (cfg.name, n_stages, total_len, comp, "serve")

    def hook(span_id, kind, shapes):
        numeric_rt.record_trace(tag + (span_id, kind, shapes))

    prog = build_session_program(cfg, n_stages, tuple(span), total_len,
                                 compress=comp, trace_hook=hook)
    with _LOCK:
        prog = _SESSIONS.setdefault(key, prog)
    return prog


def full_session_program(cfg: ArchConfig, total_len: int,
                         remat: bool = True) -> SessionProgram:
    """The whole model as one session program — the single-process
    reference path (``make_prefill_step``/``make_serve_step``) behind
    the same interface the staged spans expose.  ``kv`` is a 1-tuple
    (the model as one "stage")."""
    key = (cfg, "full", total_len, remat)
    with _LOCK:
        prog = _SESSIONS.get(key)
    if prog is not None:
        return prog
    from repro.train.steps import make_prefill_step, make_serve_step
    prefill_step = make_prefill_step(cfg, remat=remat, last_only=True,
                                     cache_len=total_len)
    serve_step = make_serve_step(cfg)

    def prefill_fn(params, tokens):
        nxt, caches = prefill_step(params, {"tokens": tokens})
        return nxt, (caches,)

    def decode_fn(params, kv, token, pos):
        nxt, caches = serve_step(params, kv[0], token, pos)
        return nxt.astype(jnp.int32), (caches,)

    tag = (cfg.name, 1, total_len, "none", "serve")

    def traced(fn, kind):
        def counted(*args):
            numeric_rt.record_trace(
                tag + ((0, 1), kind,
                       tuple(tuple(a.shape) for a in jax.tree.leaves(args)
                             if hasattr(a, "shape"))[:4]))
            return fn(*args)
        counted.__name__ = counted.__qualname__ = kind
        return jax.jit(counted)

    prog = SessionProgram(
        span=(0, 1), n_stages=1, total_len=total_len,
        prefill=traced(prefill_fn, "prefill"),
        decode=traced(decode_fn, "decode"),
        flops_per_token=(0.0 if cfg.family == "audio" else
                         _stage_fwd_flops(cfg, 0, 1, total_len, "none",
                                          False)),
        prefill_fn=prefill_fn, decode_fn=decode_fn)
    with _LOCK:
        prog = _SESSIONS.setdefault(key, prog)
    return prog
