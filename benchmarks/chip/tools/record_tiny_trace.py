"""Record the small TPU trace the trace-reduction tests read.

    python3 benchmarks/chip/tools/record_tiny_trace.py <out_dir>

Runs one jitted elementwise program and one Pallas kernel a few times on
the first chip under the JAX profiler, copies the ``.xplane.pb`` to
``<out_dir>/tiny_tpu.xplane.pb`` and prints each plane's lines with their
event counts and a few event names.
"""
import shutil
import sys
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _double(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


def main(argv):
    out = Path(argv[0])
    out.mkdir(parents=True, exist_ok=True)
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    x = jnp.ones((1024, 1024), jnp.float32)
    scale = jax.jit(lambda a: jnp.sin(a) * 3.0 + 1.0)
    double = jax.jit(lambda a: pl.pallas_call(
        _double, out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype))(a))
    jax.block_until_ready((scale(x), double(x)))
    tdir = tempfile.mkdtemp()
    jax.profiler.start_trace(tdir)
    for _ in range(3):
        jax.block_until_ready(scale(x))
        jax.block_until_ready(double(x))
    jax.profiler.stop_trace()
    src = sorted(Path(tdir).rglob("*.xplane.pb"))[-1]
    shutil.copy(src, out / "tiny_tpu.xplane.pb")
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(str(src))
    for plane in prof.planes:
        print("plane", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  line", repr(line.name), len(events),
                  [e.name for e in events[:4]])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
