"""Pallas kernel: fused decode→filter→aggregate over page blocks.

The late-materialization path (core/fused.py, DESIGN.md §7) collapses the
stage-B half of a predicated scan into ONE pallas launch per row group:
every kernel-fusable operand column — dictionary-coded (codes unpacked and
gathered in-kernel) or PLAIN 32-bit (bitcast in-kernel) — rides into the
same call together with the stage-A selection mask, and each grid step
emits one per-page float32 partial of ``sum(where(mask, left*right, 0))``.
The selected values never touch HBM as a materialized column.

Bit-identity contract: a page is a (rows, 128) tile block
(kernels/common.py), and its partial is ``page_partial``: per CHUNK_ROWS
rows, ``mask_and_select`` gives each lane's ``where(mask, left*right, 0)``;
the chunks add lane-wise into a (CHUNK_ROWS, 128) accumulator in row order,
which one final sum reduces.  The kernel body and the unfused reference
twin (``reference_page_reduce``) run this same sequence of operations on
the same values, so the per-page partials are bitwise identical wherever
both lower through the same compiler — the CI bit-identity step pins it on
the CPU.  On a TPU the kernel's final sum is Mosaic's and the twin's is
XLA's, so there identity is a measured property, not a contract.

Operand config (static, hashable) — one tuple per operand, in order:
    (kind, width, vdtype, lo, hi, lo_incl, hi_incl, in_set, role)
kind   : 'dict' (bit-transposed codes + dictionary gather) | 'plain'
         (uint32 words bitcast to vdtype)
width  : dict code bit width (0 for plain)
vdtype : 'float32' | 'int32' — decoded value dtype
lo/hi  : optional interval predicate bounds applied to this operand
in_set : optional tuple of allowed values (OR of equality tests)
role   : '' | 'left' | 'right' | 'both' — the aggregate product factors
         ('both' when the same column is squared: left == right)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels.common import (CHUNK_ROWS, ROW, count_launch,
                                  dict_rows, interpret_default, lookup_rows,
                                  padded_rows, to_rows, unpack_rows,
                                  words_to_rows)


def mask_and_select(mask, vals_list, cfg):
    """The canonical lane-wise term — shared bit-for-bit by the fused
    kernel body and the unfused reference twin.

    mask: bool tile — stage-A predicate AND validity; vals_list: one
    decoded value tile per cfg operand.  Returns the float32 tile of
    ``where(mask & predicates, left*right, 0)``.  Lanes with mask False
    contribute exactly +0.0 (``where`` selects, it never propagates the
    discarded product), which is what lets zone/selection-skipped pages be
    backfilled with literal 0.0 on the fused side without breaking
    identity.
    """
    left = right = None
    for op, vals in zip(cfg, vals_list):
        mask = apply_predicates(mask, vals, op)
        role = op[8]
        if role == "left":
            left = vals
        elif role == "right":
            right = vals
        elif role == "both":
            left = right = vals
    prod = left * right
    if prod.dtype != jnp.float32:
        prod = prod.astype(jnp.float32)
    return jnp.where(mask, prod, jnp.float32(0))


def page_partial(n_rows: int, chunk_terms):
    """The canonical page reduce: ``chunk_terms(i)`` is chunk ``i``'s
    (CHUNK_ROWS, 128) term tile; chunks accumulate lane-wise in order,
    then one sum.  Used as-is by the kernel (terms read from refs) and the
    reference twin (terms sliced from arrays)."""
    acc = lax.fori_loop(
        0, n_rows // CHUNK_ROWS, lambda i, a: a + chunk_terms(i),
        jnp.zeros((CHUNK_ROWS, ROW), jnp.float32))
    return jnp.sum(acc)


def apply_predicates(mask, vals, op):
    """Interval/set predicate of one cfg operand over decoded values —
    the same compares ``mask_and_select`` folds in, exposed for the
    stage-A mask build (host-side numpy arrays work too: the expressions
    are pure comparisons, exact in any backend)."""
    _, _, vdtype, lo, hi, lo_incl, hi_incl, in_set, _ = op
    cast = np.dtype(vdtype).type
    if lo is not None:
        mask = mask & (vals >= cast(lo) if lo_incl else vals > cast(lo))
    if hi is not None:
        mask = mask & (vals <= cast(hi) if hi_incl else vals < cast(hi))
    if in_set is not None:
        member = None
        for allowed in in_set:
            eq = vals == cast(allowed)
            member = eq if member is None else (member | eq)
        mask = mask & member
    return mask


def _kernel(*refs, cfg, dict_sizes, n_rows: int):
    """refs = mask_ref, then per operand: words_ref [, dict_ref], out_ref.

    One page per grid step: mask (1, rows, 128) uint8, dict words
    (1, rows, 128) in ``words_to_rows`` layout, dictionary (n_chunks, 128),
    plain words (1, rows, 128); out (1, 1, 128) holds the page partial in
    every lane."""
    mask_ref, out_ref = refs[0], refs[-1]

    def terms(i):
        rows = pl.ds(pl.multiple_of(i * CHUNK_ROWS, CHUNK_ROWS), CHUNK_ROWS)
        mask = mask_ref[0, rows, :] != 0
        vals_list = []
        k = 1
        sizes = iter(dict_sizes)
        for op in cfg:
            kind, width, vdtype = op[0], op[1], op[2]
            words = refs[k][0, rows, :]
            k += 1
            if kind == "dict":
                dict_ref, d = refs[k], next(sizes)
                k += 1
                codes = jnp.clip(unpack_rows(words, width).astype(jnp.int32),
                                 0, d - 1)
                bits = lookup_rows(codes, dict_ref)
                vals_list.append(jax.lax.bitcast_convert_type(
                    bits, jnp.float32) if vdtype == "float32" else bits)
            else:
                target = jnp.float32 if vdtype == "float32" else jnp.int32
                vals_list.append(jax.lax.bitcast_convert_type(words, target))
        return mask_and_select(mask, vals_list, cfg)

    out_ref[0] = jnp.full((1, ROW), page_partial(n_rows, terms), jnp.float32)


def fused_page_agg(mask, arrays, *, cfg, interpret: bool | None = None):
    """One launch: decode + filter + aggregate every page of a row group.

    mask: (n_pages, P) uint8 — stage-A predicate AND validity per lane.
    arrays: flat operand inputs matching ``cfg`` in order — for a 'dict'
    operand a (n_pages, W) uint32 words array then its (D,) dictionary;
    for a 'plain' operand a (n_pages, P) uint32 words array.  For dict
    operands W must be (P // 32) * width.

    Returns (n_pages,) float32 canonical per-page partials.
    """
    if interpret is None:
        interpret = interpret_default()
    count_launch()
    return _fused_page_agg_jit(mask, *arrays, cfg=cfg, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("cfg", "interpret"))
def _fused_page_agg_jit(mask, *arrays, cfg, interpret: bool):
    n_pages, p = mask.shape
    n_rows = padded_rows(p)
    page = pl.BlockSpec((1, n_rows, ROW), lambda i: (i, 0, 0))
    in_specs, inputs, dict_sizes = [page], [to_rows(mask, n_rows)], []
    i = 0
    for op in cfg:
        if op[0] == "dict":
            inputs.append(words_to_rows(arrays[i], op[1], n_rows))
            d = arrays[i + 1]
            drows = dict_rows(d)
            in_specs += [page, pl.BlockSpec(drows.shape, lambda i: (0, 0))]
            inputs.append(drows)
            dict_sizes.append(d.shape[0])
            i += 2
        else:
            inputs.append(to_rows(arrays[i], n_rows))
            in_specs.append(page)
            i += 1
    out = pl.pallas_call(
        functools.partial(_kernel, cfg=cfg, dict_sizes=tuple(dict_sizes),
                          n_rows=n_rows),
        grid=(n_pages,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, ROW), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pages, 1, ROW), jnp.float32),
        interpret=interpret,
    )(*inputs)
    return out[:, 0, 0]


@functools.partial(jax.jit, static_argnames=("cfg",))
def reference_page_reduce(mask_row, *vals_rows, cfg):
    """The unfused twin of one fused grid step: identical operations over
    one (1, P) page of already-materialized values, laid out as the same
    (rows, 128) tiles.  Used by the reference execution mode and the host
    decode backend, so every layer produces the same canonical bits as the
    pallas kernel."""
    n_rows = padded_rows(mask_row.shape[1])
    mask = to_rows(mask_row, n_rows)[0] != 0
    vals = [to_rows(v, n_rows)[0] for v in vals_rows]

    def terms(i):
        def rows(x):
            return lax.dynamic_slice_in_dim(x, i * CHUNK_ROWS, CHUNK_ROWS)
        return mask_and_select(rows(mask), [rows(v) for v in vals], cfg)

    return page_partial(n_rows, terms)
