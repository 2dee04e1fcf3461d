"""Record ``data/cpu_trace.xplane.pb`` and ``data/cpu_trace_hlo.txt``, the
small trace the trace-reduction tests read: three calls of one jitted
program (a matmul, a tanh and a sum) inside a ``bench/solve`` annotation,
on the CPU backend.

    JAX_PLATFORMS=cpu python chipbench/tests/record_trace.py
"""

import re
import tempfile
import time
from pathlib import Path

DATA = Path(__file__).resolve().parent / "data"


def main():
    import jax
    import jax.numpy as jnp

    # compiled from a string, so that no path of this machine is recorded
    body = eval(compile("lambda a, b: jnp.tanh(a @ b).sum(axis=0)", "<recorded>", "eval"),
                {"jnp": jnp})
    f = jax.jit(body)
    a, b = jnp.ones((512, 512)), jnp.ones((512, 8))
    f(a, b).block_until_ready()
    hlo = f.lower(a, b).compile().as_text()
    # keep the instructions, not where the recording script lay
    hlo = re.sub(r"\nFileNames\n.*?\n\n\n", "\n", hlo, flags=re.S)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench/solve"):
            for _ in range(3):
                f(a, b).block_until_ready()
                time.sleep(0.002)
        jax.profiler.stop_trace()
        raw = next(Path(d).glob("plugins/profile/*/*.xplane.pb")).read_bytes()
    # the trace names this script by its absolute path: blank it to a
    # relative name of the same length (the protobuf keeps its lengths)
    here = str(Path(__file__).resolve()).encode()
    raw = raw.replace(here, b"chipbench/tests/record_trace.py".rjust(len(here), b"_"))
    (DATA / "cpu_trace.xplane.pb").write_bytes(raw)
    (DATA / "cpu_trace_hlo.txt").write_text(hlo)


if __name__ == "__main__":
    main()
