"""Micro-benchmark: per-step cost of the fused-scan block matmul vs layout.

Isolates the kernel's inner step: DMA a [m, d] (row-major) or [d, m]
(dim-major) block, matmul against a [qt, d] query tile, reduce, write.
If the row-major variant is much slower, the main kernel's cost is the
implicit in-kernel transpose of the RHS, not DMA or merge work.
"""
import functools
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from raft_tpu.core.compile_cache import use_compile_cache  # noqa: E402

use_compile_cache()

QT, D = 128, 128
M = 8704  # rows per block (128-multiple)
N_UNITS = 128
STEPS = 512


def make(layout, reduce_kind):
    def kernel(pr_ref, q_ref, y_ref, out_ref, acc):
        j = pl.program_id(0)

        @pl.when(j == 0)
        def _():
            acc[...] = jnp.zeros((QT, 512), jnp.float32)

        q = q_ref[...]
        if layout == "md":
            y = y_ref[0]  # [M, D]
            dot = lax.dot_general(
                q, y, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        else:
            y = y_ref[0]  # [D, M]
            dot = lax.dot_general(
                q, y, dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        if reduce_kind == "slice":
            acc[...] = acc[...] + dot[:, :512]
        else:  # banked min over 128-lane groups
            r = dot[:, :512]
            for g in range(1, M // 512):
                r = jnp.minimum(r, dot[:, g * 512 : (g + 1) * 512])
            acc[...] = jnp.minimum(acc[...], r)

        @pl.when(j == STEPS - 1)
        def _():
            out_ref[...] = acc[...]

    block = (1, M, D) if layout == "md" else (1, D, M)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(STEPS,),
        in_specs=[
            pl.BlockSpec((QT, D), lambda j, pr: (0, 0)),
            pl.BlockSpec(block, lambda j, pr: (pr[j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((QT, 512), lambda j, pr: (0, 0)),
        scratch_shapes=[pltpu.VMEM((QT, 512), jnp.float32)],
    )

    @jax.jit
    def run(pr, q, y):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((QT, 512), jnp.float32),
        )(pr, q, y)

    return run


def main():
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (QT, D), jnp.bfloat16)
    pr = jnp.asarray(np.random.default_rng(0).integers(0, N_UNITS, STEPS), jnp.int32)

    for layout in ("md", "dm"):
        shape = (N_UNITS, M, D) if layout == "md" else (N_UNITS, D, M)
        y = jax.random.normal(key, shape, jnp.bfloat16)
        for reduce_kind in ("slice", "min"):
            run = make(layout, reduce_kind)
            out = run(pr, q, y)
            float(jnp.sum(out))
            best = float("inf")
            for _ in range(4):
                t0 = time.perf_counter()
                for _ in range(4):
                    out = run(pr, q, y)
                float(jnp.sum(out))
                best = min(best, (time.perf_counter() - t0) / 4)
            us = best / STEPS * 1e6
            gbps = M * D * 2 / (best / STEPS) / 1e9
            print(f"{layout} {reduce_kind:6s}: {us:8.2f} us/step  ({gbps:6.0f} GB/s eff)")


if __name__ == "__main__":
    main()
