"""Pallas kernel micro-bench. On a TPU the kernels run compiled (Mosaic);
elsewhere in interpret mode, which times the Pallas interpreter and is
correctness-grade only — every line names the mode and the device."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compat import pallas_available
from repro.kernels.ops import bitonic_stage, dense_rank_sorted, radix_histogram

from .bench_util import emit, time_call


def main():
    interpret = not pallas_available()
    tag = f"interpret={interpret};device={jax.devices()[0].device_kind}"
    rng = np.random.default_rng(0)
    d = jnp.asarray(rng.integers(0, 256, 1 << 14), jnp.int32)
    us = time_call(lambda: radix_histogram(d, 256, interpret=interpret)
                   .block_until_ready())
    emit("kernels/radix_hist/16k", us, tag)

    rows = jnp.asarray(
        np.c_[rng.integers(0, 9, (1 << 12, 4)), rng.permutation(1 << 12)],
        jnp.int32)
    us = time_call(lambda: bitonic_stage(rows, 1 << 12, 1 << 11,
                                         interpret=interpret)
                   .block_until_ready())
    emit("kernels/bitonic_stage/4k", us, tag)

    sr = jnp.sort(jnp.asarray(rng.integers(0, 64, (1 << 14, 1)), jnp.int32),
                  axis=0)
    us = time_call(lambda: dense_rank_sorted(sr, interpret=interpret)[0]
                   .block_until_ready())
    emit("kernels/dense_rank/16k", us, tag)


if __name__ == "__main__":
    main()
