"""Record a small TPU trace, to test the trace reduction on a real profile.

    python3 bench/tests/record_trace.py bench/tests/data/small_v5e.xplane.pb

On a TPU: a host span ``window`` holding a ``profile`` span in which the
toolchain's LIF scan runs on a 256-neuron network for 200 steps, then a
``partition`` span in which the host sleeps 50 ms with the device idle,
then the scan once more outside any layer span.
"""
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.snn.lif import LIFParams, _lif_scan

    if jax.devices()[0].platform != "tpu":
        print("record_trace.py: needs a TPU", file=sys.stderr)
        return 1
    rng = np.random.default_rng(0)
    w = jnp.asarray((rng.random((256, 256)) < 0.05) * 0.25, dtype=jnp.float32)
    drive = jnp.asarray((rng.random((200, 256)) < 0.1) * 1.5, dtype=jnp.float32)
    def scan():
        _lif_scan(w, drive, LIFParams(), False, False).block_until_ready()

    scan()
    with tempfile.TemporaryDirectory() as d:
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
        with jax.profiler.TraceAnnotation("window"):
            with jax.profiler.TraceAnnotation("profile"):
                scan()
            with jax.profiler.TraceAnnotation("partition"):
                time.sleep(0.05)
            scan()
        jax.profiler.stop_trace()
        found = sorted(Path(d).glob("plugins/profile/*/*.xplane.pb"))
        shutil.copy(found[-1], sys.argv[1])
    return 0


if __name__ == "__main__":
    sys.exit(main())
