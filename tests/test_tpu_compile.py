"""What the TPU's compiler makes of the kernels this repo leans on, compiled
HERE for a described v5e (no chip: structure and bytes, never a time).

One file on purpose: only one process may hold the TPU's library, so the
topology is described inside a fixture, after collection, by the one xdist
worker that is given this file; where it cannot be described the tests skip.

``core/dense.table_rows`` + ``pick_row`` at ``bitcoin5k_cities``' width (PR
51: 200 vertices, 5,000 hosts, 64 outbox slots, two lanes): the read must
hold no ``gather``, store nothing ``[V, cap, H]`` wide (the picks of all
half words share ONE compare inside one fusion) and need next to no HBM
scratch — the properties ``route_outbox``'s form past ``MAX_DENSE_VERTICES``
was chosen for (PERF.md §6, PR 51).
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from shadow1_tpu.core.dense import pick_row, table_rows

V, H, CAP, LANES = 200, 5000, 64, 2


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep it out."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def test_path_table_read_holds_no_gather_and_stores_no_v_cap_h_plane(
        one_chip, no_compile_cache):
    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def read(tables, vs_h, vd):
        return jax.vmap(lambda t, d: pick_row(table_rows(t, vs_h), d))(
            tables, vd)

    compiled = jax.jit(read).lower(
        shape((LANES, V, V), jnp.uint64), shape((H,), jnp.int32),
        shape((LANES, CAP, H), jnp.int32)).compile()
    text = compiled.as_text()
    assert " gather(" not in text
    assert " convolution(" in text            # the rows: a product on the MXU
    # What the program STORES are the results of its entry computation's
    # instructions; inside a fusion a [V, cap, H] value is never a buffer.
    entry = text[text.index("\nENTRY "):]
    wide = re.findall(rf"= [^=]*\[(?:\d+,)*{V},{CAP},{H}\]", entry)
    assert not wide, f"a [V, cap, H] plane is stored: {wide[0]}"
    # Both half words of both lanes are picked in ONE fusion.
    plane = f"u32[{LANES},{CAP},{H}]"
    picks = [ln for ln in entry.splitlines() if " fusion(" in ln
             and ln.split(" fusion(")[0].count(plane) == 2]
    assert len(picks) == 1, picks
    assert compiled.memory_analysis().temp_size_in_bytes < 16e6
