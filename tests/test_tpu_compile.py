"""Compile the main-path Pallas kernels for a described TPU v5e.

Interpret mode (every other kernel test) cannot see Mosaic's tiling rules
or VMEM limits: these compiles can. Each test compiles with
``interpret=False`` against a ``v5e:2x2`` topology that is described, not
attached, at the published widths of the model that uses the kernel:

  - ``flare_mixer_packed`` forward and gradient at ``flare_pde`` widths
    (8 heads, M=2048, D=8) on a batch of 8 x 40,000 points, at a head pack
    of 8 (16 heads, M=256: 2,048 packed latent rows), and over 200 points
    (a 208-token tile, not a multiple of 128 lanes);
  - ``paged_attention`` at ``qwen2_1_5b`` widths (2 KV heads, 6 query heads
    per KV head, D=128, 16-token pages) over bf16 and int8 pages;
  - ``flare_mixer_packed_shard`` forward and gradient on a 2x2 mesh.

Only one process may load the TPU library, so the topology is described in
a module-scoped fixture (never at import time) and the tests stay in this
one file; nothing runs, so no time or result is checked here.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

HBM_BYTES = 16 * 1024**3  # one v5e chip

# flare_pde mixer widths (configs/flare_pde.py) at the pde_40k shape
PDE = dict(B=8, H=8, M=2048, D=8, N=40000)
# the packed kernels at a heuristic pack > 1 and at a ragged token tile
PACKED = {"pack8": dict(PDE, H=16, M=256), "tile208": dict(PDE, N=200)}
# qwen2_1_5b decode read (configs/qwen2_1_5b.py): 8 slots x 2048 capacity
# over a 16,384-token pool of 16-token pages (+ the trash page)
QWEN = dict(B=8, H=2, G=6, D=128, block=16, NB=1025, P=128)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a persistent cache cannot read back entries compiled for a device
    # that is not attached; keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_BYTES
    return compiled


def _grad(f):
    return jax.grad(lambda q, k, v: jnp.sum(f(q, k, v)), argnums=(0, 1, 2))


@pytest.mark.parametrize("mode", ["forward", "grad"])
def test_flare_packed_compiles_at_flare_pde_widths(one_chip, mode):
    from repro.kernels.flare_packed import flare_mixer_packed

    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    q = s((PDE["H"], PDE["M"], PDE["D"]))
    k = s((PDE["B"], PDE["H"], PDE["N"], PDE["D"]))
    f = lambda q, k, v: flare_mixer_packed(q, k, v, interpret=False)
    _compile(f if mode == "forward" else _grad(f), q, k, k)


@pytest.mark.parametrize("shape", PACKED.values(), ids=PACKED.keys())
@pytest.mark.parametrize("mode", ["forward", "grad"])
def test_flare_packed_compiles_at_pack8_and_ragged_tile(one_chip, shape, mode):
    from repro.kernels.flare_packed import flare_mixer_packed

    s = lambda dims: jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)
    q = s((shape["H"], shape["M"], shape["D"]))
    k = s((shape["B"], shape["H"], shape["N"], shape["D"]))
    f = lambda q, k, v: flare_mixer_packed(q, k, v, interpret=False)
    _compile(f if mode == "forward" else _grad(f), q, k, k)


@pytest.mark.parametrize("dtype", ["bfloat16", "int8"])
def test_paged_attention_compiles_at_qwen2_widths(one_chip, dtype):
    from repro.kernels.paged_attention import paged_attention

    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    c = QWEN
    q = s((c["B"], c["H"], c["G"], c["D"]), jnp.float32)
    pages = s((c["NB"], c["H"], c["block"], c["D"]), jnp.dtype(dtype))
    pt = s((c["B"], c["P"]), jnp.int32)
    lengths = s((c["B"],), jnp.int32)
    scales = s((c["NB"], c["H"], c["block"]), jnp.float32)
    if dtype == "int8":
        f = lambda q, k, v, pt, ln, ks, vs: paged_attention(
            q, k, v, pt, ln, scale=0.088, k_scale=ks, v_scale=vs,
            out_dtype=jnp.bfloat16, interpret=False)
        _compile(f, q, pages, pages, pt, lengths, scales, scales)
    else:
        f = lambda q, k, v, pt, ln: paged_attention(
            q, k, v, pt, ln, scale=0.088, out_dtype=jnp.bfloat16,
            interpret=False)
        _compile(f, q, pages, pages, pt, lengths)


@pytest.mark.parametrize("mode", ["forward", "grad"])
def test_flare_packed_shard_compiles_on_2x2_mesh(topo, mode):
    from repro.kernels.flare_packed_shard import flare_mixer_packed_shard

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    q = jax.ShapeDtypeStruct((PDE["H"], PDE["M"], PDE["D"]), jnp.float32,
                             sharding=NamedSharding(mesh, P("model", None, None)))
    k = jax.ShapeDtypeStruct((PDE["B"], PDE["H"], PDE["N"], PDE["D"]),
                             jnp.float32,
                             sharding=NamedSharding(mesh, P(None, "model", "data", None)))
    f = lambda q, k, v: flare_mixer_packed_shard(q, k, v, mesh=mesh,
                                                 interpret=False)
    compiled = _compile(f if mode == "forward" else _grad(f), q, k, k)
    # the cross-shard flash merge is the only communication
    assert "all-reduce" in compiled.as_text()
