"""Record the small CPU trace that test_bench_trace_reduce.py reads.

    JAX_PLATFORMS=cpu PYTHONPATH=src python bench/tests/record_cpu_trace.py

Writes data/cpu_trace.xplane.pb and data/cpu_trace.hlo.txt beside this
file: four launches of one program, an exact ring product of a (64, 32) by a
(32, 8) matrix plus one, inside a `bench.window` annotation, with a 50 ms
`bench.sleep` between the third launch and the fourth.
"""
import glob
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp

jax.config.update("jax_enable_x64", True)

from repro.core.backend import ring_matmul  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


@jax.jit
def prog(a, b):
    return ring_matmul(a, b) + 1


def strip_source_tables(text: str) -> str:
    """The HLO without its source-location tables (FileNames and the
    rest), which name the files of the machine it was compiled on."""
    keep, skip = [], False
    for line in text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            skip = True
        elif skip and not line.strip():
            skip = False
            continue
        if not skip:
            keep.append(line)
    return "\n".join(keep) + "\n"


def main():
    a = jnp.ones((64, 32), jnp.uint64)
    b = jnp.ones((32, 8), jnp.uint64)
    prog(a, b).block_until_ready()
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.fit"):
            for _ in range(3):
                prog(a, b).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.sleep"):
            time.sleep(0.05)
        with jax.profiler.TraceAnnotation("bench.fit"):
            prog(a, b).block_until_ready()
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    out = os.path.join(HERE, "data")
    os.makedirs(out, exist_ok=True)
    shutil.copy(src[0], os.path.join(out, "cpu_trace.xplane.pb"))
    with open(os.path.join(out, "cpu_trace.hlo.txt"), "w") as fh:
        fh.write(strip_source_tables(prog.lower(a, b).compile().as_text()))
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main()
