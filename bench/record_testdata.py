"""Records the small device trace that ``tests/test_xplane.py`` reduces.

  python3 bench/record_testdata.py <output.xplane.pb>

Run on a TPU. It traces a short window shaped like the harness's: a
``bench.window`` annotation around steps of a small jitted program, each
dispatched under ``bench.dispatch`` and waited for under ``bench.wait``, with
a host sleep between steps, so that the device sits idle in known gaps.
"""
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

STEPS, SLEEP_S = 5, 0.02


def main(out: str) -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_testdata runs on a TPU")
    step = jax.jit(lambda x: jnp.tanh(x @ x) * 0.5)
    x = jax.block_until_ready(step(jnp.ones((2048, 2048), jnp.bfloat16)))
    tmp = Path(tempfile.mkdtemp())
    jax.profiler.start_trace(str(tmp))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(STEPS):
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                x = step(x)
            with jax.profiler.TraceAnnotation("bench.wait"):
                x = jax.block_until_ready(x)
            with jax.profiler.TraceAnnotation("bench.sleep"):
                time.sleep(SLEEP_S)
    jax.profiler.stop_trace()
    found = sorted(tmp.glob("plugins/profile/*/*.xplane.pb"))
    shutil.copyfile(found[-1], out)
    shutil.rmtree(tmp)
    print(f"wrote {out}: {Path(out).stat().st_size} bytes")


if __name__ == "__main__":
    main(sys.argv[1])
