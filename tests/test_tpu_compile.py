"""Compiles for a described TPU v5e, with no chip attached.

The chip's compiler is installed with JAX, so the Pallas kernels of the
main path and the BERT-Large train steps are compiled here at their real
sizes: what the compiler refuses (a block shape off the (8, 128) tiling,
a primitive Mosaic cannot lower, a program over 16 GiB of HBM) fails here
and not on the chip.  Nothing runs, so nothing here is a time.

The topology is described inside a fixture: only the worker that runs
this file loads the TPU library.
"""
import dataclasses
import os
import re
import sys
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
BENCH = os.path.join(os.path.dirname(__file__), "..", "bench")


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

    @staticmethod
    def _attention_text(one_chip, shape, **kw) -> str:
        from repro.kernels.flash_attn import ops
        x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

        def fwd_bwd(q, k, v, do):
            o, vjp = jax.vjp(lambda a, b, c: ops.flash_attention(
                a, b, c, **kw), q, k, v)
            return o, vjp(do)
        return _compile(fwd_bwd, x, x, x, x).as_text()

    @pytest.mark.parametrize("causal", [True, False])
    def test_flash_attention(self, one_chip, causal):
        """The attention op's forward and backward kernels at the
        bert-base cell's shape: 32 x 512, 12 heads of 64, bf16."""
        text = self._attention_text(one_chip, (32, 512, 12, 64),
                                    causal=causal)
        assert "flash_attn_fwd" in text and "flash_attn_bwd" in text

    @pytest.mark.parametrize("window", [None, 1000])
    def test_flash_attention_tiled(self, one_chip, window):
        """Sequence 4096 takes the online-softmax forward and the backward
        that sums dq across kv blocks, with di from the op."""
        text = self._attention_text(one_chip, (1, 4096, 8, 128),
                                    causal=True, window=window)
        assert "flash_attn_fwd" in text and "flash_attn_bwd" in text


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


def _train_step(topo, arch, batch: int, seq: int, stage: str,
                use_kernel: bool = False, chips: int = 1, pods: int = 1,
                **step_kw):
    """The donated step program of ``launch.train`` on ``chips``
    described chips (data parallel, over ``pods`` pods), from shapes
    only; ``arch`` is a registered name or a config, ``batch`` the global
    batch, ``step_kw`` further ``TrainStepConfig`` fields."""
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.train.step import (TrainStepConfig, init_train_state,
                                  make_train_step)
    cfg = get_config(arch) if isinstance(arch, str) else arch
    devices = np.array(topo.devices[:chips])
    if pods > 1:
        dp = ("pod", "data")
        mesh = Mesh(devices.reshape(pods, chips // pods, 1), dp + ("model",))
    else:
        dp = "data"
        mesh = Mesh(devices.reshape(chips, 1), ("data", "model"))
    tsc = TrainStepConfig(stage=stage, use_kernel=use_kernel, **step_kw)
    fn = make_train_step(cfg, mesh, tsc)

    def shapes(tree, specs):
        return jax.tree.map(
            lambda p, a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                              sharding=NamedSharding(mesh, p)),
            specs, tree, is_leaf=lambda x: isinstance(x, P))

    params = jax.eval_shape(lambda k: T.init_params(cfg, k, tp=1),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    opt = init_train_state(cfg, mesh, abstract=True, topology=tsc.topology,
                           optimizer=tsc.build_optimizer())
    rows = NamedSharding(mesh, P(dp, None))
    data = {k: jax.ShapeDtypeStruct((batch, seq), dt, sharding=rows)
            for k, dt in (("tokens", jnp.int32), ("labels", jnp.int32),
                          ("loss_mask", jnp.float32))}
    lr = jax.ShapeDtypeStruct((), jnp.float32,
                              sharding=NamedSharding(mesh, P()))
    t0 = time.time()
    compiled = fn.build(data).lower(shapes(params, fn.param_specs),
                                    shapes(opt, fn.opt_specs), data,
                                    lr).compile()
    return compiled, time.time() - t0


# the optimizer's Pallas kernels, by the jitted calls they are named after
OPTIMIZER_KERNELS = ("jit(_adam_call)", "jit(_ef_compress_call)",
                     "jit(_decompress_call)")
# the flat exchange's server step, by its kernel's name: in every
# compressed 1-bit program with the flat schedule, use_kernel or not
SERVER_KERNEL = "onebit_server"
# each compressed jnp-codec program's peak (GiB) with the server step
# in XLA's fusions, which materialise the average and the server buffer
SERVER_IN_XLA_PEAK_GIB = {1: 12.22, 4: 10.20}


# each program's peak (GiB) when every run also carried the hierarchical
# schedule's two cross-pod EF slots, full-length zeros on one chip
PEAK_WITH_OUTER_SLOTS_GIB = {("warmup", False): 12.90,
                             ("compressed", False): 14.94,
                             ("warmup", True): 13.58,
                             ("compressed", True): 13.58}
# what bench/run.py holds beside the state across the first compressed
# step (held_momentum): two full-length float32 vectors
HELD_BYTES = 2 * 4 * BERT_LARGE_D


@pytest.mark.parametrize("stage,use_kernel", [
    ("warmup", False), ("compressed", False), ("warmup", True),
    ("compressed", True)])
def test_bert_large_step_fits_one_chip(topo, stage, use_kernel):
    """The compiler itself refuses a program over the chip's HBM; the
    donated parameters and optimizer state are held once (aliased), and
    the state is the parameters, m, v and the two error-feedback
    vectors, with no slot the flat exchange never reads."""
    compiled, seconds = _train_step(topo, "bert-large", 32, 128, stage,
                                    use_kernel)
    ma = compiled.memory_analysis()
    print(f"{stage} kernels={use_kernel}: compiled in {seconds:.1f}s, "
          f"peak {ma.peak_memory_in_bytes / 2**30:.2f} GiB")
    # everything but the batch and the learning rate is donated: five
    # full-length float32 vectors
    assert 0 <= ma.argument_size_in_bytes - 5 * 4 * BERT_LARGE_D < 2**20
    assert ma.argument_size_in_bytes - ma.alias_size_in_bytes < 2**20
    assert ma.peak_memory_in_bytes <= HBM_BYTES
    assert ma.peak_memory_in_bytes <= (
        PEAK_WITH_OUTER_SLOTS_GIB[stage, use_kernel] - 2.5) * 2**30
    if stage == "compressed":
        assert ma.peak_memory_in_bytes + HELD_BYTES <= HBM_BYTES
    if stage == "compressed" and not use_kernel:
        assert ma.peak_memory_in_bytes <= SERVER_IN_XLA_PEAK_GIB[1] * 2**30
    text = compiled.as_text()
    # sequence 128 is below FLASH_MIN_SEQ, so attention goes through XLA;
    # the optimizer's kernels are there with use_kernel only, the server
    # kernel in every compressed step
    assert "flash_attn" not in text
    assert any(k in text for k in OPTIMIZER_KERNELS) == use_kernel
    assert (SERVER_KERNEL in text) == (stage == "compressed")
    # a minute would mean the compressed exchange fell back into the
    # pathological layouts the flat-vector views avoid
    assert seconds < 120


def test_bert_large_dp4_step_fits_beside_held_momentum(topo):
    """The compressed step of the benchmark's data-parallel cell: 128 x
    128 over a described 2x2, the flat 1-bit exchange (an all-to-all and
    an all-gather of packed signs and block scales), with room on each
    chip for the harness's two held vectors."""
    compiled, seconds = _train_step(topo, "bert-large", 128, 128,
                                    "compressed", chips=4)
    ma = compiled.memory_analysis()
    print(f"bert-large dp 4, 128 x 128: compiled in {seconds:.1f}s, peak "
          f"{ma.peak_memory_in_bytes / 2**30:.2f} GiB a chip")
    # a chip holds the parameters, m, v, its worker error and the server
    # error of its quarter of the vector
    assert 0 <= ma.argument_size_in_bytes - 4.25 * 4 * BERT_LARGE_D < 2**20
    assert ma.peak_memory_in_bytes + HELD_BYTES <= HBM_BYTES
    assert ma.peak_memory_in_bytes <= SERVER_IN_XLA_PEAK_GIB[4] * 2**30
    text = compiled.as_text()
    assert "all-to-all" in text and "all-gather" in text
    assert SERVER_KERNEL in text


@pytest.mark.parametrize("compressor,pods,fused", [
    ("onebit", 1, True), ("topk", 1, False), ("onebit", 2, False)])
def test_server_kernel_on_the_flat_onebit_exchange_only(topo, compressor,
                                                        pods, fused):
    """The server kernel replaces the flat schedule's 1-bit server step
    alone: a sparse compressor and the hierarchical schedule (two pods
    of two chips) keep the decompress, mean and compress of XLA."""
    compiled, _ = _train_step(topo, "bert-base-smoke", 8, 64, "compressed",
                              chips=4, pods=pods, compressor=compressor,
                              topology="hier" if pods > 1 else "flat")
    text = compiled.as_text()
    assert "all-to-all" in text
    assert (SERVER_KERNEL in text) == fused


def test_bert_base_step_keeps_scores_in_vmem(topo):
    """The bert-base step of the benchmark's cell (32 x 512, compressed):
    attention runs through both kernels, and no buffer of the step holds
    the 32 x 12 x 512 x 512 scores or probabilities."""
    compiled, seconds = _train_step(topo, "bert-base", 32, 512,
                                    "compressed")
    text = compiled.as_text()
    print(f"bert-base 32 x 512: compiled in {seconds:.1f}s, peak "
          f"{compiled.memory_analysis().peak_memory_in_bytes / 2**30:.2f} "
          f"GiB")
    assert "flash_attn_fwd" in text and "flash_attn_bwd" in text
    assert not re.search(r"(?:f32|bf16)\[32,12,512,512\]", text)


def test_attention_kernels_under_attention_scope(topo):
    """The kernels' forward, recomputed forward and backward carry the
    ``obs::model::attention`` layer scope in their op_name, so the
    benchmark's scope split (``harness.scopes``) counts them under
    attention, the custom_vjp's backward included."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    from harness import scopes
    from repro.configs import get_config
    # the smoke config's 64-row attn_chunk would send S = 512 chunked
    cfg = dataclasses.replace(get_config("bert-base-smoke"), attn_chunk=2048)
    compiled, _ = _train_step(topo, cfg, 2, 512, "warmup")
    found = set()
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%(flash_attn_(?:fwd|bwd))[.\d]* = ", line)
        if not m:
            continue
        src = re.search(r'op_name="([^"]*)"', line).group(1)
        scope, remat = scopes.scope_of(src)
        assert scope == "obs::model::attention", src
        found.add((m.group(1), remat, "transpose(" in src))
    assert found == {("flash_attn_fwd", False, False),   # forward
                     ("flash_attn_fwd", True, True),     # recomputed
                     ("flash_attn_bwd", False, True)}    # backward
