"""Record the small trace kept in ``benchmark/fixtures/`` (run on the chip).

    chiprun -- python3 benchmark/tools/record_fixture.py chiprun_out/fixture

Three calls of a small jitted program (a scanned pair of matmuls, so that
the trace holds a ``while`` with operations nested in it), each inside a
``bench:step`` span, with a ``bench:input_wait`` sleep between them so that
the device has idle gaps a host span covers. The tests reduce this file
and compare with the numbers written beside it.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time


def main(out_dir: str) -> int:
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    import jax
    import jax.numpy as jnp

    from benchmark.harness import xplane

    @jax.jit
    def program(x, w):
        def body(c, wi):
            return jnp.tanh(c @ wi) @ wi.T, None
        return jax.lax.scan(body, x, w)[0].sum()

    x = jnp.ones((256, 512), jnp.bfloat16)
    w = jnp.ones((4, 512, 512), jnp.bfloat16) * 0.01
    program(x, w).block_until_ready()
    log_dir = tempfile.mkdtemp(prefix="fixture_trace_")
    jax.profiler.start_trace(log_dir,
                             profiler_options=xplane.profile_options())
    t0 = time.perf_counter()
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench:step"):
            program(x, w).block_until_ready()
        with jax.profiler.TraceAnnotation("bench:input_wait"):
            time.sleep(0.003)
    window = time.perf_counter() - t0
    jax.profiler.stop_trace()
    path = xplane.find_xplane(log_dir)
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(path, os.path.join(out_dir, "small_v5e.xplane.pb"))
    reduced = xplane.reduce_trace(path)
    reduced["window_s"] = window
    reduced["device_kind"] = jax.devices()[0].device_kind
    with open(os.path.join(out_dir, "small_v5e.expected.json"), "w") as f:
        json.dump(reduced, f, indent=1)
    print(json.dumps(reduced)[:3000])
    shutil.rmtree(log_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
