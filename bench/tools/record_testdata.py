"""Record the small profiler trace that bench/tests/test_trace_reduce.py reads.

    python bench/tools/record_testdata.py <out_dir>

Runs on every device JAX finds: a jitted step of a few matmuls followed by
a psum over all devices, dispatched three times. The host annotates each
step as batch / dispatch / wait, and the second batch sleeps 50 ms, so the
trace holds one long idle gap under "batch". It writes the .xplane.pb to
<out_dir>/trace.xplane.pb and prints the planes and lines it holds.
"""
from __future__ import annotations

import glob
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def main() -> int:
    out = Path(sys.argv[1])
    out.mkdir(parents=True, exist_ok=True)
    devs = jax.devices()
    mesh = Mesh(devs, ("d",))
    sh = NamedSharding(mesh, P("d"))

    @jax.jit
    def step(x):
        def body(x):
            for _ in range(3):
                x = jnp.tanh(x @ x.T @ x) * 0.5
            return jax.lax.psum(x, "d")
        return jax.shard_map(body, mesh=mesh, in_specs=P("d"),
                             out_specs=P("d"))(x)

    x = jax.device_put(jnp.ones((len(devs) * 1024, 1024), jnp.float32) * 0.01,
                       sh)
    step(x).block_until_ready()
    tmp = tempfile.mkdtemp(dir=out)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for i in range(3):
        with jax.profiler.TraceAnnotation("batch"):
            if i == 1:
                time.sleep(0.05)
            y = x + i
        with jax.profiler.TraceAnnotation("dispatch"):
            r = step(y)
        with jax.profiler.TraceAnnotation("wait"):
            r.block_until_ready()
    jax.profiler.stop_trace()
    src = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
    shutil.copy(src, out / "trace.xplane.pb")
    shutil.rmtree(tmp)
    pd = jax.profiler.ProfileData.from_file(str(out / "trace.xplane.pb"))
    desc = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            lines.append({"line": line.name, "n": len(evs),
                          "first": [(e.name, e.start_ns, e.duration_ns)
                                    for e in evs[:6]]})
        desc.append({"plane": plane.name, "lines": lines})
    print(json.dumps({"device_kind": devs[0].device_kind, "n": len(devs),
                      "planes": desc}, indent=1, default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main())
