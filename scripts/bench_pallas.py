"""Settle the Pallas group-by kernel on hardware (ROADMAP S5).

Times the VMEM one-hot Pallas kernel (ops/pallas_groupby.py) against the
XLA one-hot matmul path it would replace, on the REAL chip, across block
sizes and group counts within the Pallas VMEM cap. Prints one JSON line
per (N, G, R) with Grows/s for both and the ratio.

Decision rule (applied by hand after a run): enable by default if the
kernel wins >=1.1x across the board, delete it if it loses — an unproven
parallel kernel is maintenance surface, not capability.

Usage: python scripts/bench_pallas.py   (needs a TPU: Mosaic compiles
nowhere else, and interpret-mode timings prove nothing)
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp
import numpy as np


def main() -> None:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench_pallas.py: needs a TPU, found {devs}; nothing emitted")
    print(f"# devices: {devs}", file=sys.stderr)

    from parseable_tpu.ops.kernels import SUM_DOT_PRECISION
    from parseable_tpu.ops.pallas_groupby import additive_groupby_pallas

    def xla_additive(ids, rows, num_groups):
        iota = jnp.arange(num_groups, dtype=jnp.int32)[None, :]
        onehot = (ids[:, None] == iota).astype(jnp.float32)
        return jax.lax.dot_general(
            rows, onehot, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=SUM_DOT_PRECISION,  # what the engine's sum dot asks for
        )

    def xla_additive_bf16(ids, rows, num_groups):
        iota = jnp.arange(num_groups, dtype=jnp.int32)[None, :]
        onehot = (ids[:, None] == iota).astype(jnp.bfloat16)
        return jax.lax.dot_general(
            rows.astype(jnp.bfloat16), onehot, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    xla_jit = jax.jit(xla_additive, static_argnames=("num_groups",))
    xla_bf16_jit = jax.jit(xla_additive_bf16, static_argnames=("num_groups",))

    rng = np.random.default_rng(0)
    for n in (1 << 20, 1 << 21):
        for g in (128, 256, 512):
            for r in (4, 8):
                ids = jax.device_put(rng.integers(0, g, n).astype(np.int32))
                rows = jax.device_put(rng.random((r, n)).astype(np.float32))
                jax.block_until_ready((ids, rows))

                def timed(fn, *args) -> float:
                    fn(*args).block_until_ready()  # compile
                    best = float("inf")
                    for _ in range(5):
                        t0 = time.perf_counter()
                        fn(*args).block_until_ready()
                        best = min(best, time.perf_counter() - t0)
                    return best

                t_xla = timed(xla_jit, ids, rows, g)
                t_bf16 = timed(xla_bf16_jit, ids, rows, g)
                try:
                    t_pallas = timed(
                        lambda i, ro, gg=g: additive_groupby_pallas(i, ro, gg),
                        ids,
                        rows,
                    )
                except Exception as e:  # noqa: BLE001
                    print(f"# pallas failed N={n} G={g} R={r}: {e}", file=sys.stderr)
                    t_pallas = float("inf")
                # parity spot check
                a = np.asarray(xla_jit(ids, rows, g))
                b = np.asarray(additive_groupby_pallas(ids, rows, g))
                ok = bool(np.allclose(a, b, rtol=1e-5, atol=1e-3))
                print(
                    json.dumps(
                        {
                            "n": n,
                            "g": g,
                            "r": r,
                            "xla_f32_grows_s": round(n / t_xla / 1e9, 2),
                            "xla_bf16_grows_s": round(n / t_bf16 / 1e9, 2),
                            "pallas_grows_s": round(n / t_pallas / 1e9, 2),
                            "pallas_vs_xla": round(t_xla / t_pallas, 2),
                            "parity": ok,
                        }
                    ),
                    flush=True,
                )


if __name__ == "__main__":
    main()
