"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Interpret mode runs a kernel's body as ordinary JAX, so it accepts block
shapes and vector layouts that the TPU compiler refuses. These tests
hand each kernel's jitted dispatch the shapes of the serve path (the
FEMNIST-shaped d=784 ragged-lane case, the bucket ladder up to n=1024)
and compile it for a v5e chip that is described, not attached: what the
chip's compiler would refuse fails here, and the compiled text must hold
the Mosaic kernel (``tpu_custom_call``). The refresh's Algorithm 2
program compiles the same way at the cells' fold-state shapes. Nothing
runs.

The topology is described inside a module fixture, never at import: one
process at a time may load the TPU library, and every test worker
imports every test file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import server
from repro.core.local_kmeans import project_top_k
from repro.kernels import kmeans_update as KU
from repro.kernels import moe_dispatch as MD
from repro.kernels import pdist_argmin as PA
from repro.kernels import solve_attach as SA

D = 784            # FEMNIST width: 6 x 128 + 16, the ragged-lane case
K, K_PRIME = 64, 8


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs
        from jax.experimental import topologies
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.fixture
def compile_text(one_chip, no_persistent_cache):
    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                for s, dt in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()
    return compile_


F32, BF16, I32, BOOL = jnp.float32, jnp.bfloat16, jnp.int32, jnp.bool_


@pytest.mark.parametrize("B,n", [(1, 64), (8, 256), (64, 1024)])
@pytest.mark.parametrize("dtype", ["f32", "bf16"])
def test_solve_attach_compiles(compile_text, B, n, dtype):
    """The fused serve step at each serve bucket (B=1 was refused for a
    lane->sublane bool reshape, B>1 for (1, n) mask blocks, n=1024 for
    exceeding the default scoped VMEM)."""
    def fn(x, c0, tau, cm, pm):
        return SA._solve_attach(x, c0, tau, cm, pm, max_iters=100,
                                dtype=dtype, interpret=False)
    text = compile_text(fn, ((B, n, D), F32), ((B, K_PRIME, D), F32),
                        ((K, D), F32), ((B, K_PRIME), BOOL), ((B, n), BOOL))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n,k,dtype", [
    (4096, K, F32),      # the 1-D (bn,) output blocks were refused here
    (4000, K, F32),      # ragged row tail
    (4096, 1024, F32),   # several k-blocks
    (4096, K, BF16),
])
def test_pairwise_argmin_compiles(compile_text, n, k, dtype):
    def fn(x, c, cm):
        return PA._pairwise_argmin(x, c, cm, bn=128, bd=512, bk=512,
                                   interpret=False)
    text = compile_text(fn, ((n, D), dtype), ((k, D), dtype), ((k,), BOOL))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("weighted", [False, True])
def test_kmeans_update_compiles(compile_text, weighted):
    def fn(x, assign, w):
        return KU._kmeans_update(x, assign, K, w if weighted else None,
                                 bn=256, interpret=False)
    text = compile_text(fn, ((4096, D), F32), ((4096,), I32),
                        ((4096,), F32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kernel", ["dispatch", "combine"])
@pytest.mark.parametrize("dtype", [F32, BF16])
def test_moe_compiles(compile_text, kernel, dtype):
    T, S, d, top_k = 1024, 2048, 1024, 2
    if kernel == "dispatch":
        def fn(x, src, valid):
            return MD._moe_dispatch(x, src, valid, bd=512, interpret=False)
        shapes = (((T, d), dtype), ((S,), I32), ((S,), BOOL))
    else:
        def fn(ybuf, slot, gates):
            return MD._moe_combine(ybuf, slot, gates, top_k=top_k, bd=512,
                                   interpret=False)
        shapes = (((S, d), dtype), ((T * top_k,), I32),
                  ((T * top_k,), F32))
    assert "tpu_custom_call" in compile_text(fn, *shapes)


@pytest.mark.parametrize("n", [64, 256, 1024])
def test_projection_compiles_without_dxd_work(compile_text, n):
    """Algorithm 1 step 1, vmapped over a batch of 64 at each serve
    bucket: no per-request (d, d) matrix and no (d + 1)-entry
    divide-and-conquer work stack, the marks of a full SVD."""
    def fn(x, kv, pm):
        return jax.vmap(lambda a, k, m: project_top_k(a, k, K_PRIME, m))(
            x, kv, pm)
    text = compile_text(fn, ((64, n, D), F32), ((64,), I32),
                        ((64, n), BOOL))
    assert f"f32[64,{D},{D}]" not in text
    assert f"s32[{D + 1}]" not in text


def test_projection_compiles_at_cifar_width(compile_text):
    """cifar100-3072's step 1: a batch of 64 reports of the one 128 pad
    at d = 3,072 (24 x 128 lanes, aligned), k' = 10; no (d, d) work."""
    def fn(x, kv, pm):
        return jax.vmap(lambda a, k, m: project_top_k(a, k, 10, m))(
            x, kv, pm)
    text = compile_text(fn, ((64, 128, 3072), F32), ((64,), I32),
                        ((64, 128), BOOL))
    assert "f32[64,3072,3072]" not in text
    assert "s32[3073]" not in text


@pytest.mark.parametrize("capacity,kp,d,k", [(4096, 8, 784, 64),
                                             (512, 10, 3072, 100)])
def test_refresh_aggregate_compiles(compile_text, capacity, kp, d, k):
    """Algorithm 2 as each refresh runs it, one compiled program over
    the fold state: FEMNIST's 4,096 x k' = 8 slots at d = 784 into
    k = 64 centers, and CIFAR-100's 512 x 10 at d = 3,072 into 100."""
    def fn(centers, mask):
        return server.aggregate(centers, mask, k)
    compile_text(fn, ((capacity, kp, d), F32), ((capacity, kp), BOOL))
