"""The ragged deployment's programs (1191^3 on mesh [2,2,1]: raw shard 602 x
602 x 1197 f32, four quantities) compiled at the REAL size for a described
v5e:2x2 -- no chip, the TPU's own compiler.  What interpret mode cannot show:
the backend's default layout of that shard (y-minor, which the domain must
pin against), Mosaic taking ``blend_slab_dynamic`` and the 1197-lane
self-wrap, and that no transient of the exchange or of a fill is a whole
array (15 GB of fields leave 1.9 GB on a 16 GB chip).  Nothing runs."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from stencil_tpu.core.radius import Radius
from stencil_tpu.domain import DistributedDomain

ARRAY = 602 * 608 * 1280 * 4  # one quantity's shard in (8,128) tiles

# a Mosaic lowering recurses deep, and pytest's own stack lies under it
# (tests/test_overlap_schedule.py has the story)
if sys.getrecursionlimit() < 10_000:
    sys.setrecursionlimit(10_000)


@pytest.fixture(scope="module")
def ragged(request):
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "true")  # else minutes of metadata retries
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    from jax.experimental.compilation_cache import compilation_cache
    from stencil_tpu.ops import halo_blend

    # the blend kernels engage as on the chip; a cross-compiled executable
    # cannot be read back from the persistent cache, so keep it out
    patch = pytest.MonkeyPatch()
    patch.setattr(halo_blend, "pallas_interpret", lambda: False)
    was, x64 = jax.config.jax_enable_compilation_cache, jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)  # as on the chip: Mosaic's index arithmetic is 32-bit
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    dd = DistributedDomain(1191, 1191, 1191)
    dd.set_radius(Radius.constant(3))
    dd.set_devices(list(topo.devices))
    handles = [dd.add_data(f"q{i}", dtype=jnp.float32) for i in range(4)]
    dd.realize(allocate=False)
    dd._plan_wrap_axes("direct")  # what realize() records with the route, once it allocates
    yield dd, handles
    patch.undo()
    jax.config.update("jax_enable_compilation_cache", was)
    jax.config.update("jax_enable_x64", x64)
    compilation_cache.reset_cache()


def _whole_array_ops(text: str) -> list:
    """Instructions of the entry computation's callees that produce a whole
    shard and are no kernel: copies, fusions, dynamic-update-slices."""
    return [l.strip()[:160] for l in text.splitlines()
            if re.search(r"= f32\[602,602,1197\]\S* (copy|fusion|dynamic-update-slice|transpose)\(", l)]


def test_the_shard_is_pinned_row_major(ragged):
    dd, handles = ragged
    assert tuple(dd.mesh_dim()) == (2, 2, 1) and dd.valid_last() == (595, 595, None)
    assert dd._uneven_axes == "xy" and dd._wrap_axes == "z"
    # the backend would store it y-minor: every quantity is pinned
    assert sorted(dd._pinned) == ["q0", "q1", "q2", "q3"]
    fmt = dd.abstract_arrays()["q0"].format
    assert tuple(fmt.layout.major_to_minor) == (0, 1, 2)


def test_the_exchange_holds_no_whole_array_transient(ragged):
    from stencil_tpu.ops.exchange import make_exchange_fn

    dd, _ = ragged
    fn = make_exchange_fn(dd.mesh, dd._shell_radius, valid_last=dd.valid_last(),
                          route="direct", out_shardings=dd._out_formats())
    compiled = fn.lower(dd.abstract_arrays()).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == mem.alias_size_in_bytes == 4 * ARRAY  # in place
    # slabs: 296 MB, sent and received of BOTH axes at once since the x and y
    # sweeps fly jointly (ISSUE 50; 75 MB when they ran in turn) -- a sixth of one array
    assert mem.temp_size_in_bytes < ARRAY // 6, mem.temp_size_in_bytes
    text = compiled.as_text()
    assert _whole_array_ops(text) == []
    assert "{1,2,0" not in text.split("ENTRY")[1].split("\n")[0]  # row-major in, row-major out
    # x low + y low (static) 8, x high + y high (traced) 8, the z self-wrap 4
    assert text.count('custom_call_target="tpu_custom_call"') == 20


def test_a_fill_holds_one_array(ragged):
    dd, handles = ragged
    fill = dd._init_program(
        handles[0], lambda x, y, z, p: ((x * 1009 + y * 31 + z + p) % (1 << 20)).astype(jnp.float32),
        False, 1)
    compiled = fill.lower(
        dd.abstract_arrays()["q0"], jax.ShapeDtypeStruct((), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == ARRAY and mem.temp_size_in_bytes < ARRAY // 64, mem
