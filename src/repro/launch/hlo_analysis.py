"""HLO post-processing for the roofline: collective-byte accounting and the
layer FLOP probe.

Collective bytes: ``compiled.as_text()`` is the *partitioned* module, so
tensor shapes are per-device.  We sum the payload bytes of every
``all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute`` op; ops inside ``while`` bodies are multiplied by the
loop trip count, recovered from the largest integer constant compared
against the induction variable in the loop's condition computation (scan
lowers to exactly that pattern).

FLOP probe: see :mod:`repro.models.probe` — XLA counts a while body once,
so the per-layer body is lowered standalone (inner chunk loops collapsed)
and totals are reconstructed as ``graph + (n-1) x layer``.
"""
from __future__ import annotations

import re
from typing import Optional

import numpy as np

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def shape_bytes(type_str: str) -> int:
    """'bf16[4096,512]{1,0}' -> byte size; tuples summed."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _split_computations(hlo: str) -> dict[str, str]:
    """computation name -> body text."""
    comps = {}
    cur_name, cur_lines = None, []
    for line in hlo.splitlines():
        m = re.match(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\([^)]*\)\s*->", line)
        if m and ("{" in line):
            if cur_name:
                comps[cur_name] = "\n".join(cur_lines)
            cur_name, cur_lines = m.group(1), [line]
        elif cur_name is not None:
            cur_lines.append(line)
            if line.startswith("}"):
                comps[cur_name] = "\n".join(cur_lines)
                cur_name, cur_lines = None, []
    if cur_name:
        comps[cur_name] = "\n".join(cur_lines)
    return comps


def _while_trip_counts(hlo: str, comps: dict[str, str]) -> dict[str, int]:
    """while body computation name -> estimated trip count."""
    trip: dict[str, int] = {}
    for m in re.finditer(
            r"while\([^)]*\), condition=%?([\w\.\-]+), body=%?([\w\.\-]+)",
            hlo):
        cond, body = m.group(1), m.group(2)
        ctext = comps.get(cond, "")
        consts = [int(c) for c in
                  re.findall(r"constant\((\d+)\)", ctext)]
        trip[body] = max(consts) if consts else 1
    return trip


def _comp_of_line_index(hlo: str) -> list[tuple[str, str]]:
    """[(computation_name, line), ...] for every op line."""
    out = []
    cur = "entry"
    for line in hlo.splitlines():
        m = re.match(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\([^)]*\)\s*->", line)
        if m and "{" in line:
            cur = m.group(1)
        out.append((cur, line))
    return out


def collective_bytes(hlo: str) -> dict:
    """Per-device payload bytes by collective kind, trip-count scaled."""
    comps = _split_computations(hlo)
    trips = _while_trip_counts(hlo, comps)
    # nested whiles: body of outer loop may contain inner while; approximate
    # by single-level scaling (scan-of-scan multiplies below).
    parents: dict[str, int] = dict(trips)

    def total_trip(comp: str, depth=0) -> int:
        # find enclosing loops: any body that calls this computation
        if depth > 4:
            return parents.get(comp, 1)
        t = parents.get(comp, 1)
        for body, bt in parents.items():
            if body == comp:
                continue
            btext = comps.get(body, "")
            if re.search(r"(condition|body)=%?" + re.escape(comp) + r"\b",
                         btext):
                t *= total_trip(body, depth + 1)
                break
        return t

    counts = {k: 0 for k in COLLECTIVES}
    bytes_ = {k: 0.0 for k in COLLECTIVES}
    ops = []
    for comp, line in _comp_of_line_index(hlo):
        for kind in COLLECTIVES:
            if re.search(r"=\s*\S*\s*" + kind + r"(\.\d+)?\(", line) or \
               re.search(r"\b" + kind + r"(-start|-done)?\(", line):
                # result type precedes '=' on the lhs:  %x = bf16[...] kind(
                mt = re.search(r"=\s*(\([^=]*?\)|[a-z0-9]+\[[^\]]*\]\S*)\s*"
                               + kind, line)
                payload = shape_bytes(mt.group(1)) if mt else 0
                scale = total_trip(comp)
                counts[kind] += 1
                bytes_[kind] += payload * scale
                ops.append({"kind": kind, "comp": comp, "bytes": payload,
                            "trip": scale})
                break
    return {"counts": counts, "bytes": bytes_,
            "total_bytes": float(sum(bytes_.values())),
            "n_ops": len(ops)}


# -------------------------------------------------------------- FLOP probe
def layer_flop_probe(cfg, shape) -> dict:
    """Lower one layer of each distinct block kind (inner loops collapsed,
    single device, global batch) and return per-kind fwd/train FLOPs +
    reconstruction constants. See repro/models/probe.py."""
    import jax
    import jax.numpy as jnp
    from repro.models import probe as probe_lib
    from repro.models import model as model_lib
    from repro.models import params as Pm
    from repro.models.blocks import REGISTRY
    from repro.models import flops as F

    B, S = shape.global_batch, shape.seq_len
    runs = model_lib.segments(cfg.block_kinds)
    kinds = sorted({k for k, _ in runs})
    out = {"kinds": {}, "runs": [[k, n] for k, n in runs],
           "n_layers": cfg.n_layers}
    decode = shape.kind == "decode"

    with probe_lib.probe_mode():
        for kind in kinds:
            specs = REGISTRY[kind][0](cfg)
            aspecs = Pm.abstract(specs)
            if decode:
                cache_sp = Pm.abstract(REGISTRY[kind][3](cfg, B, S))
                x_sp = jax.ShapeDtypeStruct((B, 1, cfg.d_model),
                                            cfg.compute_jdtype)

                def f(p, c, x):
                    pos = jnp.zeros((B, 1), jnp.int32) if cfg.rope != \
                        "mrope" else jnp.zeros((3, B, 1), jnp.int32)
                    y, _ = REGISTRY[kind][2](cfg, p, x, c,
                                             jnp.int32(S - 1), pos)
                    return jnp.sum(y.astype(jnp.float32))
                flops = _flops_of(jax.jit(f).lower(aspecs, cache_sp, x_sp))
            else:
                x_sp = jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                            cfg.compute_jdtype)
                pos = (jnp.zeros((3, B, S), jnp.int32) if cfg.rope == "mrope"
                       else jnp.arange(S))

                def f(p, x):
                    y, aux = REGISTRY[kind][1](cfg, p, x, pos)
                    return jnp.sum(y.astype(jnp.float32)) + aux
                if shape.kind == "train":
                    g = jax.grad(lambda p, x: f(p, x), argnums=(0, 1))
                    flops = _flops_of(jax.jit(g).lower(aspecs, x_sp))
                else:
                    flops = _flops_of(jax.jit(f).lower(aspecs, x_sp))
            out["kinds"][kind] = flops
            if kind == "slstm":   # time recurrence stays a loop: analytic
                per_tok = F._slstm_flops(cfg)
                mult = 3.0 if shape.kind == "train" else 1.0
                out["kinds"][kind] = per_tok * B * (1 if decode else S) \
                    * mult
    # whisper encoder layers (probe the generic attn encoder block cost)
    if cfg.encoder_layers:
        out["encoder_note"] = "enc layers approximated by attn kind"
    return out


def _flops_of(lowered) -> float:
    c = lowered.compile().cost_analysis() or {}
    return float(c.get("flops", 0.0))


def corrected_flops(record: dict, chips: int) -> Optional[float]:
    """Reconstruct total per-device FLOPs: graph + (n_r - 1) x layer_kind
    for every run (probe FLOPs are global -> divide by chips)."""
    probe = record.get("probe")
    if not probe:
        return None
    total = float(record["hlo_flops_per_device_raw"])
    for kind, n in probe["runs"]:
        if n > 1:
            total += (n - 1) * probe["kinds"][kind] / chips
    return total
