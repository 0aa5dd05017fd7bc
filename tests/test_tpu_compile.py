"""The main path's Pallas kernels compile for a TPU v5e, at real widths.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology and refuses what the chip would refuse (unaligned tiles, too much
VMEM). Each compiled program must hold the kernel as a ``tpu_custom_call``.
The topology is described inside a fixture, never at import, so that every
test worker collects the same tests and only the one running this file
loads the TPU library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssm_scan import ssm_scan
from repro.kernels.streamed_dot import streamed_dot
from repro.kernels.streamed_matmul import streamed_matmul


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")     # no compiler logs on disk
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the cache
        cache_was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


BF16, F32 = jnp.bfloat16, jnp.float32


def test_flash_attention_compiles(one_chip):
    qkv = ((2, 36, 1024, 64), BF16)
    assert "tpu_custom_call" in _compile(one_chip, flash_attention, qkv, qkv, qkv)


@pytest.mark.parametrize("q,kv", [
    ((2, 36, 2048, 64), (2, 36, 2048, 64)),      # minicpm-2b pretrain
    ((1, 48, 2048, 128), (1, 4, 2048, 128)),     # starcoder2-15b, GQA 12
    ((1, 48, 1, 128), (1, 4, 2176, 128)),        # a decode row, ragged cache
])
def test_flash_attention_chosen_tiles_compile(one_chip, q, kv):
    """The hyperstep the kernel picks from the shapes fits the chip's
    scoped VMEM (the compiler refuses a score tile too large for it)."""
    assert "tpu_custom_call" in _compile(one_chip, flash_attention, (q, BF16),
                                         (kv, BF16), (kv, BF16))


@pytest.mark.parametrize("m", [1, 4, 2048])
def test_streamed_matmul_compiles(one_chip, m):
    """minicpm-2b's MLP up-projection at decode and prefill widths."""
    text = _compile(one_chip, streamed_matmul, ((m, 2304), BF16),
                    ((2304, 5760), BF16))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [BF16, F32])
def test_ssm_scan_compiles_at_jamba_widths(one_chip, dtype):
    """d_inner 8192, d_state 16, chunk 128: every slab is tile-aligned and the
    channel slices fit VMEM."""
    seq, di, ds = 256, 8192, 16
    text = _compile(
        one_chip, lambda x, dt, b, c, a, d: ssm_scan(x, dt, b, c, a, d,
                                                     chunk=128),
        ((1, seq, di), dtype), ((1, seq, di), dtype), ((1, seq, ds), dtype),
        ((1, seq, ds), dtype), ((di, ds), F32), ((di,), F32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [BF16, F32])
def test_streamed_dot_compiles_at_1m_words(one_chip, dtype):
    n = 1 << 20
    assert "tpu_custom_call" in _compile(one_chip, streamed_dot,
                                         ((n,), dtype), ((n,), dtype))



def test_cannon_inner_level_compiles_on_2x2(topo, monkeypatch):
    """The inner Cannon runs the matmul kernel inside ``jax.shard_map`` on a
    2×2 chip grid: the kernel's output must declare the operands' varying
    mesh axes, and the rotation must be collective-permutes."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.distributed.cannon import cannon_matmul
    from repro.launch.mesh import auto_mesh

    # the model code picks its kernel path on a TPU backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = auto_mesh((2, 2), ("data", "model"), devices=topo.devices)
    spec = jax.ShapeDtypeStruct((1024, 1024), F32,
                                sharding=NamedSharding(mesh, P("data", "model")))
    text = jax.jit(lambda a, b: cannon_matmul(a, b, mesh=mesh)).lower(
        spec, spec).compile().as_text()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text
