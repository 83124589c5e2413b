"""Compiles for a described TPU v5e, with no chip attached.

The chip's compiler is installed with JAX, so the Pallas kernels of the
main path and the BERT-Large train steps are compiled here at their real
sizes: what the compiler refuses (a block shape off the (8, 128) tiling,
a primitive Mosaic cannot lower, a program over 16 GiB of HBM) fails here
and not on the chip.  Nothing runs, so nothing here is a time.

The topology is described inside a fixture: only the worker that runs
this file loads the TPU library.
"""
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

# the padded flat parameter length of bert-large on one chip, block 4096
BERT_LARGE_D = 364_564_480
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile cannot be read back from the persistent
    # cache without a chip, so keep it out of the cache
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _vec(sharding, d=BERT_LARGE_D, dtype=jnp.float32):
    return jax.ShapeDtypeStruct((d,), dtype, sharding=sharding)


class TestKernels:
    def test_onebit_ef_compress_and_decompress(self, one_chip):
        from repro.kernels.onebit import kernel as K
        x = _vec(one_chip)
        c = _compile(lambda a, e: K.ef_compress_fused(a, e, 4096), x, x)
        assert "tpu_custom_call" in c.as_text()
        # rows of 128 lanes are the flat vector's own layout: no relayout
        # copy of the 1.4 GiB operands around the kernel
        assert c.memory_analysis().temp_size_in_bytes < 2**26
        pk = _vec(one_chip, BERT_LARGE_D // 8, jnp.uint8)
        sc = _vec(one_chip, BERT_LARGE_D // 4096)
        c = _compile(lambda p, s: K.decompress(p, s, 4096), pk, sc)
        assert "tpu_custom_call" in c.as_text()
        assert c.memory_analysis().temp_size_in_bytes < 2**26

    def test_fused_adam(self, one_chip):
        from repro.kernels.fused_adam import kernel as K
        x = _vec(one_chip)
        lr = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
        c = _compile(lambda a, m, v, g, r: K.adam_step(a, m, v, g, r,
                                                       weight_decay=0.01),
                     x, x, x, x, lr)
        assert "tpu_custom_call" in c.as_text()

    @pytest.mark.parametrize("causal", [True, False])
    def test_flash_attention(self, one_chip, causal):
        from repro.kernels.flash_attn import kernel as K
        q = jax.ShapeDtypeStruct((1, 16, 512, 64), jnp.float32,
                                 sharding=one_chip)
        c = _compile(lambda a, b, v: K.flash_attention(a, b, v,
                                                       causal=causal),
                     q, q, q)
        assert "tpu_custom_call" in c.as_text()


class TestSignPack:
    """The jnp bit pack at BERT-Large length: no intermediate with a
    minor dimension of 8 (padded to 128 lanes it needed 5.8 GiB to pack
    and 21.7 GiB to unpack)."""

    def test_pack_signs(self, one_chip):
        from repro.core.compression import pack_signs
        c = _compile(pack_signs, _vec(one_chip))
        assert c.memory_analysis().temp_size_in_bytes < 2**30

    def test_unpack_signs(self, one_chip):
        from repro.core.compression import unpack_signs
        c = _compile(unpack_signs,
                     _vec(one_chip, BERT_LARGE_D // 8, jnp.uint8))
        assert c.memory_analysis().temp_size_in_bytes < 2**30


def _bert_large_step(topo, stage: str, use_kernel: bool):
    """The donated bert-large step program of ``launch.train`` on one
    described chip, batch 32 x sequence 128, from shapes only."""
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.train.step import (TrainStepConfig, init_train_state,
                                  make_train_step)
    cfg = get_config("bert-large")
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    tsc = TrainStepConfig(stage=stage, use_kernel=use_kernel)
    fn = make_train_step(cfg, mesh, tsc)

    def shapes(tree, specs):
        return jax.tree.map(
            lambda p, a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                              sharding=NamedSharding(mesh, p)),
            specs, tree, is_leaf=lambda x: isinstance(x, P))

    params = jax.eval_shape(lambda k: T.init_params(cfg, k, tp=1),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    opt = init_train_state(cfg, mesh, abstract=True,
                           optimizer=tsc.build_optimizer())
    rows = NamedSharding(mesh, P("data", None))
    batch = {k: jax.ShapeDtypeStruct((32, 128), dt, sharding=rows)
             for k, dt in (("tokens", jnp.int32), ("labels", jnp.int32),
                           ("loss_mask", jnp.float32))}
    lr = jax.ShapeDtypeStruct((), jnp.float32,
                              sharding=NamedSharding(mesh, P()))
    t0 = time.time()
    compiled = fn.build(batch).lower(shapes(params, fn.param_specs),
                                     shapes(opt, fn.opt_specs), batch,
                                     lr).compile()
    return compiled, time.time() - t0


@pytest.mark.parametrize("stage,use_kernel", [
    ("warmup", False), ("compressed", False), ("warmup", True),
    ("compressed", True)])
def test_bert_large_step_fits_one_chip(topo, stage, use_kernel):
    """The compiler itself refuses a program over the chip's HBM; the
    donated parameters and optimizer state are held once (aliased)."""
    compiled, seconds = _bert_large_step(topo, stage, use_kernel)
    ma = compiled.memory_analysis()
    print(f"{stage} kernels={use_kernel}: compiled in {seconds:.1f}s, "
          f"peak {ma.peak_memory_in_bytes / 2**30:.2f} GiB")
    # everything but the batch and the learning rate is donated
    assert ma.argument_size_in_bytes > 9 * 2**30
    assert ma.argument_size_in_bytes - ma.alias_size_in_bytes < 2**20
    assert ma.peak_memory_in_bytes <= HBM_BYTES
    assert ("tpu_custom_call" in compiled.as_text()) == use_kernel
    # a minute would mean the compressed exchange fell back into the
    # pathological layouts the flat-vector views avoid
    assert seconds < 120
