"""AOT-compile every scan kernel for a TPU v5e at the main path's widths.

Nothing runs: the TPU compiler, which is installed with jaxlib, compiles
each kernel for a described (not attached) v5e chip, so tiling, lowering
and VMEM refusals surface on any machine.  Widths are those the TPC-H
Q6/Q12 scans produce under ACCELERATOR_OPTIMIZED (10M-row row groups, 100
pages per chunk, so 100k values per page): date dictionaries of ~2.5k
entries at width 12, small flag dictionaries at widths 3-6, DELTA
orderkeys at width 1 and DELTA INT64 extendedprice cents at about 25 bits,
both at 98 blocks per page (128 after the planner's pow2 class padding),
and pow2-padded 131072-lane fused pages.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and a test worker that loads it
keeps it until it exits.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.query import q6_fused_spec
from repro.kernels.bss_decode import _bss_decode_pages_jit
from repro.kernels.cascade_decode import _cascade_decode_pages_jit
from repro.kernels.delta_decode import _delta_decode_pages_jit
from repro.kernels.dict_decode import (_dict_decode_pages_jit,
                                       _dict_decode_pages_multi_jit)
from repro.kernels.filter_agg import TILE, filter_agg_q6
from repro.kernels.fused_agg import _fused_page_agg_jit
from repro.kernels.rle_decode import _rle_decode_pages_jit

PAGES = 100               # ACCELERATOR_OPTIMIZED.target_pages_per_chunk
PAGE_VALUES = 100_000     # 10M-row row group / 100 pages
DATE_DICT = 2548          # distinct l_receiptdate values in one row group
GROUPS = -(-PAGE_VALUES // 32)
PRICE_WORDS = 98 * (3 + 32 * 25)  # 98 blocks: header + 4 miniblocks at w 25
FUSED_P = 131_072         # core/fused.py pads pages to a power of two


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but can never be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _arg(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _dict_words(width: int, pages: int = PAGES):
    return (pages, GROUPS * width), jnp.uint32


def _fused_q6_cfg() -> tuple:
    """The Q6 stage-B operands as core/fused.py builds them: shipdate
    (dict, interval), discount (dict, interval, right), quantity (dict,
    interval), extendedprice (plain, left)."""
    spec = q6_fused_spec()
    ivs = {iv.column: iv for iv in spec.predicates}

    def op(name, kind, width, role):
        iv = ivs.get(name)
        return (kind, width, "int32" if name == "l_shipdate" else "float32",
                iv.lo if iv else None, iv.hi if iv else None,
                iv.lo_incl if iv else True, iv.hi_incl if iv else False,
                None, role)

    return (op("l_shipdate", "dict", 12, ""),
            op("l_discount", "dict", 4, "right"),
            op("l_quantity", "dict", 6, ""),
            op("l_extendedprice", "plain", 0, "left"))


def _cases(s):
    """name → (jitted entry point, positional arg specs, static kwargs)."""
    a = lambda shape, dt: _arg(s, shape, dt)          # noqa: E731
    n_blocks = 128
    fused_words = FUSED_P // 32
    return {
        "dict_shared_date": (_dict_decode_pages_jit,
                             [a(*_dict_words(12)), a((DATE_DICT,),
                                                     jnp.int32)],
                             dict(width=12)),
        "dict_shared_flag": (_dict_decode_pages_jit,
                             [a(*_dict_words(4)), a((11,), jnp.float32)],
                             dict(width=4)),
        "dict_multi_dates": (_dict_decode_pages_multi_jit,
                             [a(*_dict_words(12, 3 * PAGES)),
                              a((3 * PAGES, DATE_DICT), jnp.int32)],
                             dict(width=12)),
        "delta_orderkey": (_delta_decode_pages_jit,
                           [a((PAGES, 3430), jnp.uint32),
                            a((PAGES, n_blocks * 4), jnp.int32),
                            a((PAGES, n_blocks * 4), jnp.int32),
                            a((PAGES, n_blocks), jnp.int32),
                            a((PAGES, 1), jnp.int32)],
                           dict(n_blocks=n_blocks)),
        "delta_price": (_delta_decode_pages_jit,
                        [a((PAGES, PRICE_WORDS), jnp.uint32),
                         a((PAGES, n_blocks * 4), jnp.int32),
                         a((PAGES, n_blocks * 4), jnp.int32),
                         a((PAGES, n_blocks), jnp.int32),
                         a((PAGES, 1), jnp.int32)],
                        dict(n_blocks=n_blocks)),
        "rle": (_rle_decode_pages_jit,
                [a((PAGES, 8192), jnp.int32), a((PAGES, 8192), jnp.int32)],
                dict(n_out=131_072)),
        "fused_q6": (_fused_page_agg_jit,
                     [a((PAGES, FUSED_P), jnp.uint8),
                      a((PAGES, fused_words * 12), jnp.uint32),
                      a((DATE_DICT,), jnp.int32),
                      a((PAGES, fused_words * 4), jnp.uint32),
                      a((11,), jnp.float32),
                      a((PAGES, fused_words * 6), jnp.uint32),
                      a((50,), jnp.float32),
                      a((PAGES, FUSED_P), jnp.uint32)],
                     dict(cfg=_fused_q6_cfg())),
        "bss": (_bss_decode_pages_jit,
                [a((PAGES, PAGE_VALUES), jnp.uint32)],
                dict(stride_words=PAGE_VALUES // 4, n_out=PAGE_VALUES)),
        "cascade": (_cascade_decode_pages_jit,
                    [a((PAGES, 128 * 13), jnp.uint32),
                     a((PAGES, 128 * 8), jnp.uint32)],
                    dict(value_width=13, count_width=8, n_runs=4096,
                         n_out=38_912)),
        "filter_agg": (filter_agg_q6,
                       [a((TILE * 1221,), jnp.int32)]
                       + [a((TILE * 1221,), jnp.float32)] * 3,
                       dict(lo=731, hi=1096, dlo=0.05, dhi=0.07,
                            qmax=24.0)),
    }


KERNELS = ["dict_shared_date", "dict_shared_flag", "dict_multi_dates",
           "delta_orderkey", "delta_price", "rle", "fused_q6", "bss",
           "cascade", "filter_agg"]


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args, static = _cases(one_chip)[name]
    compiled = fn.lower(*args, interpret=False, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # whole-call HBM footprint stays far inside one v5e's 16 GB
    assert mem.temp_size_in_bytes < 4 << 30
