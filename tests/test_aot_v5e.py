"""Compiles for a TPU v5e that is described, not attached (libtpu's own
compiler, no chip): what the chip's compiler does with a program at the
benchmark's real size, which no CPU run shows. Nothing runs, so nothing
here is a time or a value. One file, and the topology only inside a
fixture: one process may load libtpu, and only a test of this file does."""
import re

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from lambdagap_tpu.objectives.rank import _lambdarank_bucket
from lambdagap_tpu.ops.partition import position_leaf

# instructions that move no data and so take no device time
FREE = re.compile(r"= \S+ (bitcast|parameter|constant|get-tuple-element|"
                  r"tuple)\(")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("n", [10_500_000, 3_408_630],
                         ids=["higgs", "istella-s"])
def test_position_leaf_keeps_its_scope_through_the_tpu_compiler(one_chip, n):
    """Every instruction of the compiled step that touches N positions
    carries the scope it was traced under: the device metrics select by
    that name, and XLA's own split of a long 1-D cumulative sum drops it."""
    leaves = jax.ShapeDtypeStruct((255,), jnp.int32, sharding=one_chip)

    def scoped(begin, count):
        with jax.named_scope("row_leaf"):
            return position_leaf(begin, count, n)

    compiled = jax.jit(scoped).lower(leaves, leaves).compile()
    text = compiled.as_text()
    nameless = [
        line.strip()[:120] for line in text[text.index("ENTRY"):].splitlines()
        if re.search(r"= \(?s32\[\d{5,}", line) and not FREE.search(line)
        and "/row_leaf/" not in line]
    assert nameless == []
    # and it streams: no temporary beyond a few N-vectors
    assert compiled.memory_analysis().temp_size_in_bytes <= 4 * 4 * n


@pytest.mark.parametrize("L", [1024, 2048])
def test_no_gain_lookup_per_pair_cell_survives_the_tpu_compiler(one_chip, L):
    """``istella-s``'s two longest buckets (``[51, 1024]`` and ``[1, 2048]``:
    the configuration's fixed multiset of query lengths), target ``ndcg``,
    truncation 30, sigmoid 1, norm: the compiled bucket kernel holds no
    gather of a pair block's size. A lookup of ``label_gain`` per pair cell
    compiled to two stand-alone ``f32[53477376]`` gathers in the
    1,024-bucket (19 % of the cell's iteration; PR 34); per document it is
    one of ``nq x L``."""
    from benchmark import manifest
    from benchmark.datagen.mslr_like import query_lengths
    cfg = manifest.load_json("configs", "istella-s.json")
    sizes = query_lengths(cfg["num_queries"], cfg["num_docs"],
                          cfg["assumed"]["query_length_median"],
                          cfg["assumed"]["query_length_longest"])
    nq = int(np.sum((sizes > L // 2) & (sizes <= L)))
    assert nq == {1024: 51, 2048: 1}[L]
    obj = cfg["objective_params"]

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = _lambdarank_bucket.lower(
        arg((nq, L)), arg((nq, L)), arg((nq, L), jnp.bool_), arg((nq,)),
        arg((nq,)), arg((32,)), target=obj["target"],
        sigmoid=obj["sigmoid"], norm=obj["norm"],
        truncation_level=obj["truncation_level"], lambdagap_weight=1.0,
        tile=None).compile().as_text()
    gathers = [math.prod(int(d) for d in m.group(1).split(",") if d)
               for m in re.finditer(r"= \w+\[([\d,]*)\]\S* gather\(", text)]
    # what is left: the sort's own gathers and the per-document lookup (the
    # one-query bucket's XLA turns into selects by itself), nq x L each
    assert gathers and max(gathers) <= nq * L < L * L, gathers
