"""Record the small trace that ``test_bench_trace.py`` reduces.

    python3 bench/tests/record_trace.py <out.xplane.pb>

Runs on the chip: a jitted matrix product over rows split across every
chip, with a sum over all of them (an all-reduce where there are several
chips), three times under the profiler with the harness's host spans and
a short host wait between the steps.
"""
import shutil
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from bench import harness as H  # noqa: E402


def main(out: str) -> None:
    devs = H.device_check(1)
    mesh = jax.make_mesh((len(devs),), ("d",))
    x = jax.device_put(jnp.ones((len(devs) * 512, 2048), jnp.bfloat16),
                       NamedSharding(mesh, P("d")))
    w = jnp.full((2048, 2048), 1e-3, jnp.bfloat16)

    @jax.jit
    def step(x, w):
        y = jnp.tanh(x @ w)
        return y, jnp.sum(y.astype(jnp.float32))

    jax.block_until_ready(step(x, w))
    with H.traced(True) as tr:
        for _ in range(3):
            with H.span("bench.step"):
                jax.block_until_ready(step(x, w))
            with H.span("bench.host_wait"):
                time.sleep(0.003)
    shutil.copy(tr.path, out)
    H.remove_trace(tr)
    print(f"recorded {out} on {len(devs)} x {devs[0].device_kind}")


if __name__ == "__main__":
    main(sys.argv[1])
