"""Record the small trace with named scopes, and the optimized HLO of its
program, that ``test_bench_scopes.py`` reads the scopes of.

    python3 bench/tests/record_scoped_trace.py <dir>/<name>.xplane.pb

writes the trace there and the HLO to ``<dir>/<name>.hlo.txt``.

Runs on one chip: a jitted step of three parts, a matrix product under
``jax.named_scope("mlp")``, a weight update under ``jax.named_scope("opt")``
and a running sum over the rows under no scope, three times under the
profiler with the harness's host spans and a short host wait between the
steps.  The HLO is compiled once more after the trace, as the scope
readers compile the cell's step (``bench/scopes.py``).
"""
import shutil
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import harness as H  # noqa: E402


def main(out: str) -> None:
    devs = H.device_check(1)
    x = jax.device_put(jnp.ones((512, 2048), jnp.bfloat16), devs[0])
    w = jax.device_put(jnp.full((2048, 2048), 1e-3, jnp.bfloat16), devs[0])

    @jax.jit
    def step(x, w):
        with jax.named_scope("mlp"):
            y = jnp.tanh(x @ w)
        with jax.named_scope("opt"):
            w = w - 1e-3 * (x.T @ y)
        return w, jnp.cumsum(y.astype(jnp.float32), axis=0)

    jax.block_until_ready(step(x, w))
    with H.traced(True) as tr:
        for _ in range(3):
            with H.span("bench.step"):
                jax.block_until_ready(step(x, w))
            with H.span("bench.host_wait"):
                time.sleep(0.003)
    shutil.copy(tr.path, out)
    H.remove_trace(tr)
    hlo = out[:-len(".xplane.pb")] + ".hlo.txt"
    with open(hlo, "w") as f:
        f.write(step.lower(x, w).compile().as_text())
    print(f"recorded {out} and {hlo} on {len(devs)} x {devs[0].device_kind}")


if __name__ == "__main__":
    main(sys.argv[1])
