"""Record the small chip trace that tests/test_devtrace.py reads.

    python3 benchmarks/chip/tests/record_fixture.py   # on one TPU chip

Runs a few small jitted programs and the Pallas fedavg kernel under the
profiler, with host sleeps between them so the device has idle gaps, and
copies the ``.xplane.pb`` to ``tests/data/small.xplane.pb``.
"""
import glob
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[2] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels.fedavg import fedavg_pallas  # noqa: E402


def main():
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_fixture: needs a TPU")
    x = jnp.ones((4, 1 << 20), jnp.float32)
    w = jnp.full((4,), 0.25, jnp.float32)
    mm = jax.jit(lambda a: (a @ a.T).sum())
    a = jnp.ones((1024, 1024), jnp.float32)
    jax.block_until_ready((fedavg_pallas(x, w), mm(a)))
    out = tempfile.mkdtemp()
    jax.profiler.start_trace(out)
    for _ in range(3):
        jax.block_until_ready(fedavg_pallas(x, w))
        time.sleep(0.002)
        jax.block_until_ready(mm(a))
        time.sleep(0.002)
    jax.profiler.stop_trace()
    (src,) = glob.glob(f"{out}/**/*.xplane.pb", recursive=True)
    (HERE / "data").mkdir(exist_ok=True)
    shutil.copy(src, HERE / "data" / "small.xplane.pb")
    shutil.rmtree(out)
    print("recorded", (HERE / "data" / "small.xplane.pb").stat().st_size, "bytes")


if __name__ == "__main__":
    main()
