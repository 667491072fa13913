"""Compiles for a TPU v5e that is described, not attached (libtpu's own
compiler, no chip): what the chip's compiler does with a program at the
benchmark's real size, which no CPU run shows. Nothing runs, so nothing
here is a time or a value. One file, and the topology only inside a
fixture: one process may load libtpu, and only a test of this file does."""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

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
