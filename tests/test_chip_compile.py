"""Compile the device path for a TPU v5e chip that is described, not
attached: the scheduler's two solver kernels at the shapes of a full
2048-job Fig. 5 consult, and the three Pallas kernels compiled (not
interpreted) at real model widths.  Nothing runs; a compile that passes
here is not a chip run.  The topology is described inside a fixture so
that only the test process given this file loads the TPU compiler."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import batch_solver as bs

N_JOBS = 2048                     # Fig. 5's largest queue
HBM_BYTES = 16 * 2 ** 30          # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep it out of there
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def fig5_state():
    from benchmarks.fig5_scalability import grown_cluster
    from repro.core.pricing import PriceState
    from repro.core.trace import philly_trace
    from repro.core.utility import effective_throughput
    cluster = grown_cluster(N_JOBS)
    jobs = philly_trace(n_jobs=N_JOBS, seed=1, types=cluster.gpu_types)
    ps = PriceState(cluster, jobs, 7 * 24 * 3600.0, effective_throughput,
                    0.0)
    return jobs, ps, effective_throughput


class _Captured(Exception):
    pass


def _capture_dispatch(monkeypatch, getter: str, run) -> tuple:
    """The kernel ``run`` dispatches through ``bs.<getter>`` and its
    arguments, stopped before anything executes."""
    seen = {}
    build = getattr(bs, getter)

    def spy(*key):
        kern = build(*key)

        def record(*args):
            seen["call"] = (kern, args)
            raise _Captured
        return record

    monkeypatch.setattr(bs, getter, spy)
    with pytest.raises(_Captured):
        run()
    return seen["call"]


def _compile(fn, args, sharding):
    shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
              for a in args]
    return fn.lower(*shapes).compile()


def _fits_one_chip(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < HBM_BYTES, used


def test_pricing_kernel_compiles_for_v5e(one_chip, fig5_state,
                                         monkeypatch):
    jobs, ps, util = fig5_state
    kern, args = _capture_dispatch(
        monkeypatch, "_get_kernel",
        lambda: bs.find_alloc_batch(jobs, ps.free_arr.copy(),
                                    ps.gamma_arr.copy(), ps, 0.0, util))
    assert args[2].shape == (N_JOBS,)      # the whole queue, one bucket
    with bs.enable_x64():
        compiled = _compile(kern, args, one_chip)
    _fits_one_chip(compiled)
    # integer selection structure only: no float64 reaches the kernel
    # beyond the cached float64 free-count view it casts on entry
    assert [str(a.dtype) for a in args[2:]] == [
        "int32", "int32", "int32", "bool", "bool", "int32", "int32"]


def test_scan_commit_kernel_compiles_for_v5e(one_chip, fig5_state,
                                             monkeypatch):
    jobs, ps, util = fig5_state
    kern, args = _capture_dispatch(
        monkeypatch, "_get_commit_kernel",
        lambda: bs._scan_commit(jobs, ps.free_arr.copy(),
                                ps.gamma_arr.copy(), ps, 0.0, util))
    assert args[5].shape == (N_JOBS,)
    with bs.enable_x64():
        compiled = _compile(kern, args, one_chip)
    _fits_one_chip(compiled)


def _pallas_cases():
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.rmsnorm import rmsnorm
    from repro.kernels.rwkv6_scan import rwkv6_scan
    bf = jnp.bfloat16
    # llama3.2-1b attention: 32 query heads, 8 kv heads, head dim 64
    attn = [jax.ShapeDtypeStruct((1, h, 2048, 64), bf) for h in (32, 8, 8)]
    # rwkv6-7b WKV: 64 heads of 64
    wkv = [jax.ShapeDtypeStruct((1, 64, 2048, 64), bf)] * 4 + [
        jax.ShapeDtypeStruct((64, 64), bf),
        jax.ShapeDtypeStruct((1, 64, 64, 64), jnp.float32)]
    # RMSNorm over rwkv6-7b's d_model
    norm = [jax.ShapeDtypeStruct((2048, 4096), bf),
            jax.ShapeDtypeStruct((4096,), bf)]
    return {
        "flash_attention": (lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=False), attn),
        "rwkv6_scan": (lambda *a: rwkv6_scan(*a, interpret=False), wkv),
        "rmsnorm": (lambda x, s: rmsnorm(x, s, interpret=False), norm),
    }


@pytest.mark.parametrize("name", ["flash_attention", "rwkv6_scan",
                                  "rmsnorm"])
def test_pallas_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _pallas_cases()[name]
    compiled = _compile(jax.jit(fn), shapes, one_chip)
    assert "tpu_custom_call" in compiled.as_text()
    _fits_one_chip(compiled)
