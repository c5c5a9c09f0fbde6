"""Compile the fold step the scan cell drives, for a described TPU v5e
chip, on a machine without one.

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 perfbench/tools/compile_check.py

The step is ``core.streaming._stream_chunk_jit`` over the paper-jobs
group (mean, median sketch, std) with a row mask, B = 256, one 2^20-row
chunk.  It prints the compile time, whether the Pallas kernel is in the
compiled program (``tpu_custom_call``) and the compiler's memory
analysis.  What the chip's compiler refuses, it raises here.
"""
from __future__ import annotations

import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import harness  # noqa: E402


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.core.reduce_api import (Mean, Quantile, StatisticGroup, Std,
                                       split_params)
    from repro.core.streaming import _stream_chunk_jit

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    # the ops layer picks the Pallas kernels only on a TPU backend
    jax.default_backend = lambda: "tpu"

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def compile_step(name, fn, stat, B, n):
        spec, params = split_params(stat)
        states = jax.eval_shape(lambda: jax.vmap(
            lambda _: stat.init_state(1))(jnp.arange(B)))
        est = jax.eval_shape(lambda: stat.init_state(1))
        put = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda a: sds(a.shape, a.dtype), t)
        t0 = time.perf_counter()
        compiled = fn.lower(put(states), put(est), sds((n, 1), jnp.float32),
                            sds((n,), jnp.float32), sds((), jnp.int32),
                            sds((), jnp.int32), params, spec, B).compile()
        print(f"{name}: compiled in {time.perf_counter() - t0:.1f} s, "
              f"Pallas kernel {'tpu_custom_call' in compiled.as_text()}, "
              f"{compiled.memory_analysis()}", flush=True)

    paper = harness.find_cell("paper-jobs.scan").config
    group = StatisticGroup((
        Mean(), Quantile(paper["median_q"], nbins=paper["median_bins"],
                         lo=paper["median_lo"], hi=paper["median_hi"]),
        Std()))
    compile_step("scan: group stream step, masked", _stream_chunk_jit, group,
                 paper["scan_resamples"], paper["scan_chunk_rows"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
