"""The Gated DeltaNet mixer's pointwise stages (``models/qwen3_next.py
GatedDeltaNet``, steps 4 and 6 of its docstring): what stands between the
projection ``in_proj_qkvz`` and the scan, and between the scan and
``out_proj``.

*Before the scan* (:func:`short_conv_l2norm`; the scope ``gdn_conv``): the
``q | k | v`` columns of ``qkvz`` through a causal depthwise convolution of
``taps`` taps (zeros before token 0), SiLU, and on q and k the per-head
``x * rsqrt(sum x^2 + 1e-6)``, q also scaled by ``dk^-1/2``.

*After the scan* (:func:`gated_rms_norm`; the scope ``gdn_gate_norm``): per
head ``norm_scale * o * rsqrt(mean o^2 + eps) * silu(z)``, ``z`` the last
columns of ``qkvz``.

**One algorithm on two paths**, chosen by what a call shows
(:func:`gdn_pointwise_plan`: backend, dtype, head sizes, length; no flag and
no model name), counted on the compile event by form (``gdn_pointwise:
{"fused": n, "composed": m}``, a mixer call site each):

*The fused passes* (a TPU, both head sizes the same whole number of lane
tiles, a length of whole token tiles).  Every stage works on the flat ``(B,
S, H d)`` layout that the projection writes and ``ops/gated_delta.py``'s
kernels read: a head is ``d / 128`` lane tiles of a token's row, so its
reduction is a lane reduction and no ``(B, S, H, d)`` tensor, float32 copy
or transpose exists.  One read and one write of each activation a pass,
float32 only in VMEM, one rounding on the way out.  q, k and v are one call:
a grid step takes a key head's q and k columns and its group's v columns,
three blocks of one slab (a block's column index carries the range's
offset: nothing is sliced or concatenated), and writes three arrays; the
taps' three rows of history are a second, 16-row block of the same columns.
(Head counts that no one grid serves — a range that starts at no multiple
of its block — take a call a range.)  The backward of each pair is written
by hand (``jax.custom_vjp``): it reads the cotangent and the same slab,
recomputes the pre-activation, the SiLU and the norm in VMEM, walks the
token tiles from the last so that the three rows of ``d pre`` a tile owes
its predecessor are a VMEM scratch, and sums the taps' and ``norm_scale``'s
gradients in float32 blocks that stay in VMEM along that axis.  Residuals:
``qkvz`` and the taps; ``o``, ``qkvz`` and ``norm_scale``.  A kernel walks
its tile 64 rows and a head at a time in a loop of two steps an iteration:
small code, because the train program's compile pays for every instruction
of every kernel instance (the whole tile unrolled was 1.2 x faster in the
kernels, 0.5 % of the step, and 130 s of set-up).

*The composed form* (every other call: a CPU, ``qwen3_next_tiny``'s head
sizes 16 and 24, a length that is no whole number of tiles — nothing is
padded): the convolution and SiLU in the slab's dtype, the norms in float32
over ``(B, S, H, d)``, autodiff through them.

Precision: the kernels read the slab's dtype, compute every stage in
float32 (the taps as the float32 parameters they are) and round once on
writing; the composed form rounds the convolution to the slab's dtype
before the float32 norms.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs.compilation import note_gdn_pointwise

_L2_EPS = 1e-6  # the source's ``l2norm``
_TOKEN_TILE = 512  # tokens a grid step; a shorter sequence is one step
_LANE_TILE = 256  # the widest section's channels a grid step, in whole heads
# tokens a step of a kernel's walk down a tile (a head's float32 value is 8
# vregs), and the steps an iteration of its loop holds.  A kernel's code is
# what the train program's compile pays for: the whole tile as one block of
# code ran the kernels 1.2 x faster and cost set-up 130 s (PERF.md §6)
_ROWS = 64
_PAIR = 2
_HALO = 16  # rows of the block before a tile: one bf16 sublane tile
_CARRY = 8  # rows handed from one loop step to the next: one float32 tile


def gdn_pointwise_plan(
    backend: str, dtype, dk: int, dv: int, seq_len: int
) -> int | None:
    """The tokens a grid step of the fused passes takes, or ``None`` for
    the composed form: a pure function of what the call shows.  The kernels
    take a TPU, bf16 or float32 (the tests' exactness), one head size for
    keys and values in whole lane tiles (a head's reduction is then a lane
    reduction inside a token's row, and every column range of ``qkvz``
    starts at a whole head) and a length of whole token tiles — up to
    ``_TOKEN_TILE`` tokens one tile of whole loop steps, or a multiple of
    ``_TOKEN_TILE``: nothing is padded."""
    if backend != "tpu" or jnp.dtype(dtype) not in (jnp.bfloat16, jnp.float32):
        return None
    if dk != dv or dk % 128 or dk > _LANE_TILE:
        return None
    tile = min(seq_len, _TOKEN_TILE)
    if tile <= 0 or tile % _ROWS or seq_len % tile:
        return None
    return tile


class _Section(NamedTuple):
    """A column range of ``qkvz`` with what a kernel does to it."""

    offset: int  # its first column in ``qkvz``
    width: int
    head: int  # lanes a head
    normalise: bool = False  # the convolution's: a per-head l2-norm follows
    scale: float = 1.0  # and multiplies its result


def _sections(hk, hv, d):
    keys, values = hk * d, hv * d
    return (
        _Section(0, keys, d, True, d ** -0.5),  # q
        _Section(keys, keys, d, True),  # k
        _Section(2 * keys, values, d),  # v
    )


def _channel_tiles(sections) -> int | None:
    """The channel tiles of a grid that serves ``sections`` in one call: a
    step takes ``width / n`` channels of each — whole heads, at most
    ``_LANE_TILE``, a divisor of the section's offset (a block's column
    index is in blocks).  The fewest such ``n``, or ``None``; a section
    alone always has one (a head a step)."""
    most = math.gcd(*(sec.width // sec.head for sec in sections))
    for n in range(1, most + 1):
        if most % n == 0 and all(
            sec.width // n <= _LANE_TILE and sec.offset % (sec.width // n) == 0
            for sec in sections
        ):
            return n
    return None


def _calls(sections):
    """``sections`` as the kernel calls that work them: one call for all
    (q, k and v of one key head's group a grid step) where one grid serves
    them, else a call each."""
    if _channel_tiles(sections) is None:
        return tuple((sec,) for sec in sections)
    return (tuple(sections),)


# ------------------------------------------------------------ composed form


def _l2_normalised(x):
    """Per head over its last axis, in float32 (the source's ``l2norm``)."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + _L2_EPS)


def _composed_conv(qkvz, w, hk, hv, dk, dv):
    b, s, _ = qkvz.shape
    keys, values, taps = hk * dk, hv * dv, w.shape[1]
    dtype = qkvz.dtype
    w = w.astype(dtype)
    with jax.named_scope("gdn_conv"):
        u = jnp.pad(qkvz[..., : 2 * keys + values], ((0, 0), (taps - 1, 0), (0, 0)))
        mixed = jax.nn.silu(sum(w[:, j] * u[:, j:j + s] for j in range(taps)))
    q, k, v = jnp.split(mixed, (keys, 2 * keys), axis=-1)
    q = (_l2_normalised(q.reshape(b, s, hk, dk)) * dk ** -0.5).astype(dtype)
    k = _l2_normalised(k.reshape(b, s, hk, dk)).astype(dtype)
    return q, k, v.reshape(b, s, hv, dv)


def _composed_gate_norm(o, qkvz, scale, eps):
    b, s, hv, dv = o.shape
    z = qkvz[..., -hv * dv:].reshape(b, s, hv, dv)
    with jax.named_scope("gdn_gate_norm"):
        o = o.astype(jnp.float32)
        o = scale * o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
        o = (o * jax.nn.silu(z.astype(jnp.float32))).astype(qkvz.dtype)
    return o.reshape(b, s, hv * dv)


# ------------------------------------------------------------- dispatchers


def short_conv_l2norm(
    qkvz, conv_kernel, *, key_heads: int, value_heads: int, key_dim: int,
    value_dim: int, interpret: bool = False,
):
    """``qkvz (B, S, 2 keys + 2 values)`` and the taps ``conv_kernel (2 keys
    + values, taps)`` float32 to ``q, k (B, S, Hk, dk)`` and ``v (B, S, Hv,
    dv)`` in ``qkvz``'s dtype, as the scan takes them (module docstring).
    Counts the mixer's call site by the form that runs it, for both of its
    stages: :func:`gated_rms_norm` makes the same choice from the same
    call.  ``interpret=True`` runs the kernels through the Pallas
    interpreter whatever the backend (the tier-1 tests, on a CPU)."""
    b, s, _ = qkvz.shape
    hk, hv, dk, dv = key_heads, value_heads, key_dim, value_dim
    tile = gdn_pointwise_plan(
        "tpu" if interpret else jax.default_backend(), qkvz.dtype, dk, dv, s
    )
    note_gdn_pointwise("composed" if tile is None else "fused")
    if tile is None:
        return _composed_conv(qkvz, conv_kernel, hk, hv, dk, dv)
    q, k, v = _fused_conv(
        qkvz, conv_kernel.astype(jnp.float32), _sections(hk, hv, dk), tile, interpret
    )
    return q.reshape(b, s, hk, dk), k.reshape(b, s, hk, dk), v.reshape(b, s, hv, dv)


def gated_rms_norm(
    o, qkvz, norm_scale, *, key_dim: int, eps: float, interpret: bool = False
):
    """The scan's ``o (B, S, Hv, dv)``, gated by the last ``Hv dv`` columns
    of ``qkvz`` and scaled by ``norm_scale (dv,)`` float32, to ``(B, S, Hv
    dv)`` in ``qkvz``'s dtype for ``out_proj`` (module docstring).
    ``key_dim`` is there for the plan: the mixer's two stages take one
    form."""
    b, s, hv, dv = o.shape
    tile = gdn_pointwise_plan(
        "tpu" if interpret else jax.default_backend(), qkvz.dtype, key_dim, dv, s
    )
    if tile is None:
        return _composed_gate_norm(o, qkvz, norm_scale, eps)
    gate = _Section(qkvz.shape[-1] - hv * dv, hv * dv, dv)
    return _fused_gate_norm(
        o.astype(qkvz.dtype).reshape(b, s, hv * dv), qkvz,
        norm_scale.astype(jnp.float32), gate, eps, tile, interpret,
    )


# ------------------------------------------------------------- the kernels


def _aligned(start, size):
    """``size`` rows from ``start``, a multiple of ``size``."""
    if not isinstance(start, int):
        start = pl.multiple_of(start, size)
    return pl.ds(start, size)


def _rows(i):
    """The ``i``-th ``_ROWS`` rows of a tile."""
    return _aligned(i * _ROWS, _ROWS)


def _walk(steps, step, carry):
    """``carry = step(i, carry)`` for ``i`` in ``range(steps)``: a loop of
    ``_PAIR`` steps an iteration, the second's loads and chain beside the
    first's."""
    pair = _PAIR if steps % _PAIR == 0 else 1

    def some(n, carry):
        for u in range(pair):
            carry = step(n * pair + u, carry)
        return carry

    if steps == pair:
        return some(0, carry)
    return jax.lax.fori_loop(0, steps // pair, some, carry)


def _heads(ref, sec):
    """The lanes of each head of a section's block."""
    return [
        slice(h * sec.head, (h + 1) * sec.head)
        for h in range(ref.shape[2] // sec.head)
    ]


def _f32(ref, *index):
    return ref[index].astype(jnp.float32)


def _fold(x):
    """``(rows, lanes)`` summed to one float32 tile of 8 rows: whole-vreg
    adds, the last 8 to 1 left to the caller's one sum outside."""
    return sum(x[i:i + _CARRY] for i in range(0, x.shape[0], _CARRY))


def _silu_parts(pre):
    sig = jax.nn.sigmoid(pre)
    return sig, pre * sig


def _d_silu(pre, sig):
    return sig * (1.0 + pre * (1.0 - sig))


def _earlier(x, before, r):
    """Row ``t`` is ``x[t - r]``, ``before`` (8 rows) standing before
    ``x``'s first: a sublane rotation of the two stacked."""
    return pltpu.roll(jnp.concatenate([before, x], axis=0), r, 0)[_CARRY:]


def _later(x, after, r):
    """Row ``t`` is ``x[t + r]``, ``after`` (8 rows) following ``x``'s
    last."""
    rows = x.shape[0] + _CARRY
    return pltpu.roll(jnp.concatenate([x, after], axis=0), rows - r, 0)[:-_CARRY]


def _taps_of(w_ref, lanes):
    """The taps of a head's channels, newest token's first: ``pre[t] = sum_r
    taps[r] x[t - r]`` (``conv_kernel[:, j]`` meets ``x[t + j - (taps -
    1)]``)."""
    n = w_ref.shape[0]
    return [w_ref[n - 1 - r:n - r, lanes] for r in range(n)]


def _pre_activation(x, before, taps):
    shifted = [x] + [_earlier(x, before, r) for r in range(1, len(taps))]
    return sum(w * u for w, u in zip(taps, shifted)), shifted


def _history(halo_ref, lanes, first_tile):
    """The 8 rows before a tile's first: zeros before token 0."""
    rows = _f32(halo_ref, 0, slice(None), lanes)[_HALO - _CARRY:]
    return jnp.where(first_tile, 0.0, rows)


def _conv_fwd_kernel(*refs, sections):
    """A ``(tokens, channels)`` tile of each section: convolution, SiLU, the
    per-head norm.  ``refs``: a section's slab tile, the 16 rows of the same
    columns before it and its taps, section after section; then a result
    tile each."""
    first_tile = pl.program_id(2) == 0
    for s, sec in enumerate(sections):
        x_ref, halo_ref, w_ref = refs[3 * s:3 * s + 3]
        o_ref = refs[3 * len(sections) + s]
        for lanes in _heads(x_ref, sec):
            taps = _taps_of(w_ref, lanes)

            def step(i, before, x_ref=x_ref, o_ref=o_ref, sec=sec, lanes=lanes, taps=taps):
                x = _f32(x_ref, 0, _rows(i), lanes)
                pre, _ = _pre_activation(x, before, taps)
                _, y = _silu_parts(pre)
                if sec.normalise:
                    ss = jnp.sum(y * y, axis=1, keepdims=True)
                    y = y * (jax.lax.rsqrt(ss + _L2_EPS) * sec.scale)
                o_ref[0, _rows(i), lanes] = y.astype(o_ref.dtype)
                return x[-_CARRY:]

            _walk(
                x_ref.shape[1] // _ROWS, step, _history(halo_ref, lanes, first_tile)
            )


def _conv_bwd_kernel(*refs, sections):
    """The same tiles from the cotangents of their results, the token tiles
    and a tile's steps walked from the last.  ``refs``: a section's slab
    tile, halo, taps and cotangent tile, section after section; then a ``d
    slab`` tile each, the taps' gradient each (8 partial rows a tap, summed
    along the token axis) and a scratch each that holds the first rows of
    ``d pre`` of the tile after (zeros after the last token)."""
    n = len(sections)
    step_of_grid, tiles = pl.program_id(2), pl.num_programs(2)
    first_tile = step_of_grid == tiles - 1  # the walk's last: token 0

    @pl.when(step_of_grid == 0)
    def _start():
        for ref in refs[5 * n:]:
            ref[...] = jnp.zeros_like(ref)

    for s, sec in enumerate(sections):
        x_ref, halo_ref, w_ref, g_ref = refs[4 * s:4 * s + 4]
        dx_ref, dw_ref, owed = refs[4 * n + s], refs[5 * n + s], refs[6 * n + s]
        steps = x_ref.shape[1] // _ROWS
        for lanes in _heads(x_ref, sec):
            taps = _taps_of(w_ref, lanes)
            history = _history(halo_ref, lanes, first_tile)

            def step(
                m, carry, x_ref=x_ref, g_ref=g_ref, dx_ref=dx_ref, sec=sec,
                steps=steps, lanes=lanes, taps=taps, history=history,
            ):
                after, dw = carry
                i = steps - 1 - m
                x = _f32(x_ref, 0, _rows(i), lanes)
                # the 8 rows before the step's: the tile's own, or its history
                start = (max if isinstance(i, int) else jnp.maximum)(
                    i * _ROWS - _HALO, 0
                )
                own = _f32(x_ref, 0, _aligned(start, _HALO), lanes)[_HALO - _CARRY:]
                pre, shifted = _pre_activation(x, jnp.where(i == 0, history, own), taps)
                sig, y = _silu_parts(pre)
                dy = _f32(g_ref, 0, _rows(i), lanes)
                if sec.normalise:  # out = scale y r, r = rsqrt(sum y^2 + eps)
                    r = jax.lax.rsqrt(jnp.sum(y * y, axis=1, keepdims=True) + _L2_EPS)
                    along = jnp.sum(dy * y, axis=1, keepdims=True)
                    dy = (sec.scale * r) * (dy - y * (r * r * along))
                dpre = dy * _d_silu(pre, sig)
                dx = taps[0] * dpre + sum(
                    taps[r] * _later(dpre, after, r) for r in range(1, len(taps))
                )
                dx_ref[0, _rows(i), lanes] = dx.astype(dx_ref.dtype)
                dw = tuple(acc + _fold(dpre * u) for acc, u in zip(dw, shifted))
                return dpre[:_CARRY], dw

            zero = jnp.zeros((_CARRY, sec.head), jnp.float32)
            after, dw = _walk(steps, step, (owed[:, lanes], (zero,) * len(taps)))
            owed[:, lanes] = after
            for r, acc in enumerate(dw):  # tap r is conv_kernel[:, taps - 1 - r]
                at = slice((len(taps) - 1 - r) * _CARRY, (len(taps) - r) * _CARRY)
                dw_ref[0, at, lanes] += acc


def _gate_fwd_kernel(o_ref, z_ref, w_ref, y_ref, *, gate, eps):
    w = w_ref[...]
    for lanes in _heads(o_ref, gate):

        def step(i, carry, lanes=lanes):
            o, z = _f32(o_ref, 0, _rows(i), lanes), _f32(z_ref, 0, _rows(i), lanes)
            r = jax.lax.rsqrt(jnp.mean(o * o, axis=1, keepdims=True) + eps)
            _, silu = _silu_parts(z)
            y_ref[0, _rows(i), lanes] = (w * (o * r) * silu).astype(y_ref.dtype)
            return carry

        _walk(o_ref.shape[1] // _ROWS, step, 0)


def _gate_bwd_kernel(g_ref, o_ref, z_ref, w_ref, do_ref, dz_ref, dw_ref, *, gate, eps):
    """``y = w n silu(z)``, ``n = o r``, ``r = rsqrt(mean o^2 + eps)``:
    ``do``, ``dz`` and 8 partial rows of ``dw`` summed over the tile's heads
    and along the token axis."""

    @pl.when(pl.program_id(2) == 0)
    def _start():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    w = w_ref[...]
    for lanes in _heads(o_ref, gate):

        def step(i, dw, lanes=lanes):
            o, z = _f32(o_ref, 0, _rows(i), lanes), _f32(z_ref, 0, _rows(i), lanes)
            g = _f32(g_ref, 0, _rows(i), lanes)
            r = jax.lax.rsqrt(jnp.mean(o * o, axis=1, keepdims=True) + eps)
            n = o * r
            sig, silu = _silu_parts(z)
            dn = g * w * silu
            along = jnp.mean(dn * n, axis=1, keepdims=True)
            do_ref[0, _rows(i), lanes] = (r * (dn - n * along)).astype(do_ref.dtype)
            gn = g * n
            dz_ref[0, _rows(i), lanes] = (gn * w * _d_silu(z, sig)).astype(dz_ref.dtype)
            return dw + _fold(gn * silu)

        dw_ref[0, 0] += _walk(
            o_ref.shape[1] // _ROWS, step, jnp.zeros((_CARRY, gate.head), jnp.float32)
        )


# ---------------------------------------------------------------- the calls


def _grid_call(kernel, name, grid, in_specs, out_specs, out_shape, scratch, interpret):
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret, name=name,
    )


def _conv_specs(sec, lanes, tile, tiles, n_taps, *, backward):
    """A section's block specs over the grid ``(B, channel tiles, token
    tiles)`` — the token axis last, sequential, the backward's walked from
    the last tile: the slab's tile and the 16 rows before it (the first
    tile's are masked in the kernel) at the section's column offset, the
    taps ``(taps, C)``, and a tile of a ``(B, S, width)`` array of the
    section's own."""
    first = sec.offset // lanes
    at = (lambda j: tiles - 1 - j) if backward else (lambda j: j)
    per_tile = tile // _HALO
    slab = pl.BlockSpec((1, tile, lanes), lambda b, c, j: (b, at(j), first + c))
    halo = pl.BlockSpec(
        (1, _HALO, lanes),
        lambda b, c, j: (b, jnp.maximum(at(j) * per_tile - 1, 0), first + c),
    )
    taps = pl.BlockSpec((n_taps, lanes), lambda b, c, j: (0, first + c))
    own = pl.BlockSpec((1, tile, lanes), lambda b, c, j: (b, at(j), c))
    return slab, halo, taps, own


def _conv_forward(qkvz, w_t, sections, tile, interpret):
    """The sections of one call to their ``(B, S, width)`` results."""
    b, s, _ = qkvz.shape
    n, tiles = _channel_tiles(sections), s // tile
    specs = [
        _conv_specs(sec, sec.width // n, tile, tiles, w_t.shape[0], backward=False)
        for sec in sections
    ]
    return _grid_call(
        functools.partial(_conv_fwd_kernel, sections=sections), "gdn_conv_fwd",
        (b, n, tiles), [spec for group in specs for spec in group[:3]],
        [group[3] for group in specs],
        [jax.ShapeDtypeStruct((b, s, sec.width), qkvz.dtype) for sec in sections],
        [], interpret,
    )(*(qkvz, qkvz, w_t) * len(sections))


def _conv_backward(qkvz, w_t, cotangents, sections, tile, interpret):
    """``d slab`` of each section of one call, ``(B, S, width)``, and the
    taps' gradient ``(width, taps)``."""
    b, s, _ = qkvz.shape
    n, tiles, n_taps = _channel_tiles(sections), s // tile, w_t.shape[0]
    specs = [
        _conv_specs(sec, sec.width // n, tile, tiles, n_taps, backward=True)
        for sec in sections
    ]
    partial_rows = [
        pl.BlockSpec((1, n_taps * _CARRY, sec.width // n), lambda b, c, j: (b, 0, c))
        for sec in sections
    ]
    shape = jax.ShapeDtypeStruct
    results = _grid_call(
        functools.partial(_conv_bwd_kernel, sections=sections), "gdn_conv_bwd",
        (b, n, tiles), [spec for group in specs for spec in group],
        [group[3] for group in specs] + partial_rows,
        [shape((b, s, sec.width), qkvz.dtype) for sec in sections]
        + [shape((b, n_taps * _CARRY, sec.width), jnp.float32) for sec in sections],
        [pltpu.VMEM((_CARRY, sec.width // n), jnp.float32) for sec in sections],
        interpret,
    )(*(x for g in cotangents for x in (qkvz, qkvz, w_t, g)))
    dxs, dws = results[:len(sections)], results[len(sections):]
    # (B, taps x 8 partial rows, width) -> (width, taps)
    return dxs, [
        dw.reshape(b, n_taps, _CARRY, -1).sum(axis=(0, 2)).T for dw in dws
    ]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _fused_conv(qkvz, w, sections, tile, interpret):
    """``w`` float32 ``(channels, taps)``; q, k, v as ``(B, S, H d)``."""
    with jax.named_scope("gdn_conv"):
        w_t = w.T
        return tuple(
            out for call in _calls(sections)
            for out in _conv_forward(qkvz, w_t, call, tile, interpret)
        )


def _fused_conv_fwd(qkvz, w, sections, tile, interpret):
    return _fused_conv(qkvz, w, sections, tile, interpret), (qkvz, w)


def _fused_conv_bwd(sections, tile, interpret, residuals, cotangents):
    qkvz, w = residuals
    cotangents = [g.astype(qkvz.dtype) for g in cotangents]
    with jax.named_scope("gdn_conv"):
        w_t, dslab, dw, done = w.T, [], [], 0
        for call in _calls(sections):
            dxs, dws = _conv_backward(
                qkvz, w_t, cotangents[done:done + len(call)], call, tile, interpret
            )
            dslab, dw, done = dslab + list(dxs), dw + dws, done + len(call)
        rest = qkvz.shape[-1] - sum(sec.width for sec in sections)
        dslab.append(jnp.zeros(qkvz.shape[:2] + (rest,), qkvz.dtype))
        return jnp.concatenate(dslab, axis=-1), jnp.concatenate(dw, axis=0)


_fused_conv.defvjp(_fused_conv_fwd, _fused_conv_bwd)


def _gate_specs(gate, tile):
    """Over the grid ``(B, channel tiles, token tiles)``: a ``(B, S, Hv
    dv)`` array's tile, ``z``'s in ``qkvz`` at the gate's column offset, and
    ``norm_scale`` as ``(1, dv)``; and the grid's channel tiles."""
    n = _channel_tiles((gate,))
    lanes = gate.width // n
    first = gate.offset // lanes
    own = pl.BlockSpec((1, tile, lanes), lambda b, c, j: (b, j, c))
    z = pl.BlockSpec((1, tile, lanes), lambda b, c, j: (b, j, first + c))
    scale = pl.BlockSpec((1, gate.head), lambda b, c, j: (0, 0))
    return (own, z, scale), n


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _fused_gate_norm(o, qkvz, scale, gate, eps, tile, interpret):
    b, s, _ = o.shape
    (own, z, w), n = _gate_specs(gate, tile)
    with jax.named_scope("gdn_gate_norm"):
        return _grid_call(
            functools.partial(_gate_fwd_kernel, gate=gate, eps=eps),
            "gdn_gate_norm_fwd", (b, n, s // tile), [own, z, w], own,
            jax.ShapeDtypeStruct(o.shape, o.dtype), [], interpret,
        )(o, qkvz, scale.reshape(1, -1))


def _fused_gate_norm_fwd(o, qkvz, scale, gate, eps, tile, interpret):
    return _fused_gate_norm(o, qkvz, scale, gate, eps, tile, interpret), (o, qkvz, scale)


def _fused_gate_norm_bwd(gate, eps, tile, interpret, residuals, g):
    o, qkvz, scale = residuals
    b, s, _ = o.shape
    (own, z, w), n = _gate_specs(gate, tile)
    partial_rows = pl.BlockSpec(
        (1, 1, _CARRY, gate.head), lambda b, c, j: (b, c, 0, 0)
    )
    shape = jax.ShapeDtypeStruct
    with jax.named_scope("gdn_gate_norm"):
        do, dz, dw = _grid_call(
            functools.partial(_gate_bwd_kernel, gate=gate, eps=eps),
            "gdn_gate_norm_bwd", (b, n, s // tile),
            [own, own, z, w], [own, own, partial_rows],
            [
                shape(o.shape, o.dtype), shape(o.shape, qkvz.dtype),
                shape((b, n, _CARRY, gate.head), jnp.float32),
            ],
            [], interpret,
        )(g.astype(o.dtype), o, qkvz, scale.reshape(1, -1))
        dqkvz = jnp.pad(dz, ((0, 0), (0, 0), (gate.offset, 0)))
        return do, dqkvz, dw.sum(axis=(0, 1, 2))


_fused_gate_norm.defvjp(_fused_gate_norm_fwd, _fused_gate_norm_bwd)
