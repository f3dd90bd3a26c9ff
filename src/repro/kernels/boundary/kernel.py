"""Pallas TPU kernels: the fused boundary-codec crossing (paper App. J
codecs + §4.3 quantize-on-send in one kernel launch per direction).

The two-pass jnp sequence this replaces (``compression/codecs.py`` +
``dist/pipeline.py::boundary_crossing``) materializes the c-dim wire
tensor in HBM between the codec matmul and the quantizer; here one grid
step loads a [ROW_TILE, d] activation tile into VMEM, runs LayerNorm ->
``w_c`` matmul (or maxout pooling) -> LayerNorm -> blockwise-int8
quantize entirely in registers/VMEM, and writes only the wire payload.
The mirror kernel dequantizes + decodes on the receiving side.

TPU mapping: rows = flattened (batch x seq) tokens, tiled at ROW_TILE;
``w_c``/``w_d`` ride along whole (c is small — the wire width), so the
matmuls hit the MXU at [ROW_TILE, d] x [d, c], accumulating in f32 (the
MXU's only accumulator).  Quantization blocks (``qb``) subdivide the
trailing wire dim, matching ``repro.kernels.boundary.ref`` bit-for-bit.
A block of 64 is half a 128-lane vreg row, and the TPU compiler refuses
to split the lane dim, so blocks (and maxout pools) are formed on the
transposed tile, where they split the sublane dim instead: the tile
rides as [c, ROW_TILE] while it is blocked.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret

ROW_TILE = 128


def _row_tile(rows: int) -> int:
    t = min(ROW_TILE, rows)
    while rows % t:
        t //= 2
    return t


def _ln32(x32: jax.Array) -> jax.Array:
    """LayerNorm core on an f32 tile (mirrors compression.bottleneck._ln)."""
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    return (x32 - mu) * jax.lax.rsqrt(var + 1e-6)


def _blocks_t(z32: jax.Array, qb: int) -> jax.Array:
    """[rows, c] tile -> [c // qb, qb, rows]: each quantization block on
    the sublanes of one slab (see the module docstring)."""
    rows, c = z32.shape
    return z32.T.reshape(c // qb, qb, rows)


def _quant_blocks(blocks: jax.Array):
    scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True)
    q = jnp.clip(jnp.round(blocks / jnp.maximum(scale, 1e-12) * 127.0),
                 -127, 127)
    return q, scale


def _qdq32(z32: jax.Array, qb: int) -> jax.Array:
    """In-register row-blocked int8 round trip on an f32 tile."""
    rows, c = z32.shape
    q, scale = _quant_blocks(_blocks_t(z32, qb))
    return (q * scale / 127.0).reshape(c, rows).T


def _quant32(z32: jax.Array, qb: int):
    rows, c = z32.shape
    q, scale = _quant_blocks(_blocks_t(z32, qb))
    return (q.reshape(c, rows).T.astype(jnp.int8), scale[:, 0, :].T)


def _encode32(x, w_ref, *, mode, k):
    """Codec encode on one tile, mirroring boundary.ref.encode_ref's
    dtype discipline (f32 norm cores, matmul in the activation dtype)."""
    dt = x.dtype
    z = _ln32(x.astype(jnp.float32)).astype(dt)
    if mode == "bottleneck":
        z = jnp.dot(z, w_ref[...].astype(dt),
                    preferred_element_type=jnp.float32).astype(dt)
        z = _ln32(z.astype(jnp.float32)).astype(dt)
    else:                                        # maxout: param-free pool
        rows, d = z.shape
        z = (z.astype(jnp.float32).T.reshape(d // k, k, rows).max(1).T
             .astype(dt))
    return z


def _decode32(z, w_ref, *, mode):
    dt = z.dtype
    if mode == "maxout":
        z = _ln32(z.astype(jnp.float32)).astype(dt)
    return jnp.dot(z, w_ref[...].astype(dt),
                   preferred_element_type=jnp.float32).astype(dt)


# ----------------------------------------------------------- kernel bodies
def _qdq_kernel(x_ref, o_ref, *, qb):
    x = x_ref[...]
    o_ref[...] = _qdq32(x.astype(jnp.float32), qb).astype(o_ref.dtype)


def _encode_kernel(x_ref, w_ref, o_ref, *, mode, k, qb, quantize):
    z = _encode32(x_ref[...], w_ref, mode=mode, k=k)
    if quantize:
        z = _qdq32(z.astype(jnp.float32), qb).astype(z.dtype)
    o_ref[...] = z.astype(o_ref.dtype)


def _encode_nw_kernel(x_ref, o_ref, *, mode, k, qb, quantize):
    z = _encode32(x_ref[...], None, mode=mode, k=k)
    if quantize:
        z = _qdq32(z.astype(jnp.float32), qb).astype(z.dtype)
    o_ref[...] = z.astype(o_ref.dtype)


def _encode_quant_kernel(x_ref, w_ref, q_ref, s_ref, *, mode, k, qb):
    z = _encode32(x_ref[...], w_ref, mode=mode, k=k)
    q, s = _quant32(z.astype(jnp.float32), qb)
    q_ref[...], s_ref[...] = q, s


def _encode_quant_nw_kernel(x_ref, q_ref, s_ref, *, mode, k, qb):
    z = _encode32(x_ref[...], None, mode=mode, k=k)
    q, s = _quant32(z.astype(jnp.float32), qb)
    q_ref[...], s_ref[...] = q, s


def _decode_kernel(z_ref, w_ref, o_ref, *, mode):
    o_ref[...] = _decode32(z_ref[...], w_ref, mode=mode).astype(o_ref.dtype)


def _dequant_decode_kernel(q_ref, s_ref, w_ref, o_ref, *, mode, qb):
    rows, c = q_ref.shape
    blocks = _blocks_t(q_ref[...].astype(jnp.float32), qb)
    z = (blocks * s_ref[...].T[:, None, :] / 127.0).reshape(c, rows).T
    z = z.astype(o_ref.dtype)
    o_ref[...] = _decode32(z, w_ref, mode=mode).astype(o_ref.dtype)


# ------------------------------------------------------------- call plumbing
def _rows_call(body, x2d, w, out_shapes, interpret):
    """Tile the leading (rows) dim; any ``w`` rides along whole, already
    in the activation dtype the kernel bodies multiply in (an f32
    [4096, 1024] ``w_c`` alone would fill the 16 MiB of scoped VMEM)."""
    rows = x2d.shape[0]
    t = _row_tile(rows)
    in_specs = [pl.BlockSpec((t, x2d.shape[1]), lambda i: (i, 0))]
    args = [x2d]
    if w is not None:
        in_specs.append(pl.BlockSpec(w.shape, lambda i: (0, 0)))
        args.append(w.astype(x2d.dtype))
    single = not isinstance(out_shapes, (list, tuple))
    outs = [out_shapes] if single else list(out_shapes)
    out_specs = [pl.BlockSpec((t, o.shape[1]), lambda i: (i, 0))
                 for o in outs]
    res = pl.pallas_call(
        body, grid=(rows // t,), in_specs=in_specs,
        out_specs=out_specs[0] if single else out_specs,
        out_shape=outs[0] if single else outs,
        interpret=resolve_interpret(interpret),
    )(*args)
    return res


def _flatten_rows(x: jax.Array):
    c = x.shape[-1]
    return x.reshape(-1, c), x.shape


# ------------------------------------------------------------- public ops
def qdq(x: jax.Array, qb: int, interpret: Optional[bool] = None):
    """Fused single-pass row-blocked int8 round trip over the trailing
    dim (the two quant8 kernel launches collapsed into one)."""
    x2d, shape = _flatten_rows(x)
    out = _rows_call(functools.partial(_qdq_kernel, qb=qb), x2d, None,
                     jax.ShapeDtypeStruct(x2d.shape, x.dtype), interpret)
    return out.reshape(shape)


def qdq_flat(x: jax.Array, block: int, interpret: Optional[bool] = None):
    """Flat-blocked fused round trip matching
    ``compression.quant8._roundtrip`` exactly (any shape; pads the tail
    block with zeros, which never raises an absmax)."""
    shape, dtype = x.shape, x.dtype
    flat = x.reshape(-1).astype(jnp.float32)
    pad = (-flat.shape[0]) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    out = qdq(flat.reshape(-1, block), block, interpret).reshape(-1)
    if pad:
        out = out[:out.shape[0] - pad]
    return out.reshape(shape).astype(dtype)


def encode(x: jax.Array, w: Optional[jax.Array], mode: str, k: int,
           qb: int, quantize: bool,
           interpret: Optional[bool] = None) -> jax.Array:
    """Fused codec encode (+ optional in-kernel QDQ): [..., d] -> the
    [..., c] float wire tensor, one kernel launch."""
    x2d, shape = _flatten_rows(x)
    c = x2d.shape[1] // k if mode == "maxout" else w.shape[1]
    out_shape = jax.ShapeDtypeStruct((x2d.shape[0], c), x.dtype)
    if mode == "maxout":
        body = functools.partial(_encode_nw_kernel, mode=mode, k=k, qb=qb,
                                 quantize=quantize)
        out = _rows_call(body, x2d, None, out_shape, interpret)
    else:
        body = functools.partial(_encode_kernel, mode=mode, k=k, qb=qb,
                                 quantize=quantize)
        out = _rows_call(body, x2d, w, out_shape, interpret)
    return out.reshape(*shape[:-1], c)


def encode_quantize(x: jax.Array, w: Optional[jax.Array], mode: str,
                    k: int, qb: int, interpret: Optional[bool] = None):
    """Fused encode + quantize emitting the actual wire payload:
    (int8 codes [..., c], f32 scales [..., c//qb])."""
    x2d, shape = _flatten_rows(x)
    c = x2d.shape[1] // k if mode == "maxout" else w.shape[1]
    outs = [jax.ShapeDtypeStruct((x2d.shape[0], c), jnp.int8),
            jax.ShapeDtypeStruct((x2d.shape[0], c // qb), jnp.float32)]
    if mode == "maxout":
        body = functools.partial(_encode_quant_nw_kernel, mode=mode, k=k,
                                 qb=qb)
        q, s = _rows_call(body, x2d, None, outs, interpret)
    else:
        body = functools.partial(_encode_quant_kernel, mode=mode, k=k,
                                 qb=qb)
        q, s = _rows_call(body, x2d, w, outs, interpret)
    return (q.reshape(*shape[:-1], c),
            s.reshape(*shape[:-1], c // qb))


def decode(z: jax.Array, w: jax.Array, mode: str,
           interpret: Optional[bool] = None) -> jax.Array:
    """Fused codec decode: [..., c] float wire -> [..., d]."""
    z2d, shape = _flatten_rows(z)
    d = w.shape[1]
    out = _rows_call(functools.partial(_decode_kernel, mode=mode), z2d, w,
                     jax.ShapeDtypeStruct((z2d.shape[0], d), z.dtype),
                     interpret)
    return out.reshape(*shape[:-1], d)


def dequantize_decode(q: jax.Array, s: jax.Array, w: jax.Array, mode: str,
                      qb: int, dtype=jnp.float32,
                      interpret: Optional[bool] = None) -> jax.Array:
    """Mirror of :func:`encode_quantize`: one kernel launch from wire
    codes + scales to the decoded [..., d] hidden state."""
    c = q.shape[-1]
    q2d = q.reshape(-1, c)
    s2d = s.reshape(-1, c // qb)
    d = w.shape[1]
    rows = q2d.shape[0]
    t = _row_tile(rows)
    out = pl.pallas_call(
        functools.partial(_dequant_decode_kernel, mode=mode, qb=qb),
        grid=(rows // t,),
        in_specs=[pl.BlockSpec((t, c), lambda i: (i, 0)),
                  pl.BlockSpec((t, c // qb), lambda i: (i, 0)),
                  pl.BlockSpec(w.shape, lambda i: (0, 0))],
        out_specs=pl.BlockSpec((t, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), dtype),
        interpret=resolve_interpret(interpret),
    )(q2d, s2d, w.astype(dtype))
    return out.reshape(*q.shape[:-1], d)
