"""Measure the cross-process (DCN-tier) link with the 2-process rig
(round 5, VERDICT r4 #6).

The simulator's ICI constants are chip-calibrated (apps/calibrate,
protocol v3), but its DCN side was an assumed 25 GB/s (machine.py
Topology).  This probe measures the EFFECTIVE cross-process all-reduce
bandwidth and latency on the same 2-process rig that executes and audits
the two-tier plans (tests/test_two_tier.py): two workers, each with half
the virtual devices, time a psum over the process axis at two volumes;
the slope gives bandwidth, the intercept latency — the reference's two
bandwidth constants were modeled, not measured
(ref:scripts/simulator.cc:37-38); here the rig's tier constant is a
measurement.

The fitted constants parameterize the simulator's own hierarchical
all-reduce model (sim/collectives._allreduce): for a 2-group reduce of
per-device volume v the cross term is t = v/bw + 2*lat, so the recorded
bw/lat plug back in consistently.  "Effective" means link sharing by the
concurrent per-device pairs is absorbed into the constant — exactly what
the list-scheduling simulator wants.

    python -m flexflow_tpu.utils.dcn_probe -o examples/strategies/dcn_calibration.json

Consumed by ``apps/search.py --dcn-calibration <file>`` (feeds
Topology.from_calibration) so two-tier searches of THIS rig run on
measured tier constants.  The TPU-pod DCN default in Topology remains the
documented model for real multi-slice deployments.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import textwrap

_WORKER = textwrap.dedent('''
import json, sys, time
pid, port, half = int(sys.argv[1]), sys.argv[2], int(sys.argv[3])
import os
os.environ["XLA_FLAGS"] = \
    "--xla_force_host_platform_device_count=%d" % half
import jax
jax.config.update("jax_platforms", "cpu")
from flexflow_tpu import distributed
machine = distributed.initialize(coordinator_address="localhost:" + port,
                                 num_processes=2, process_id=pid)
import numpy as np
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
dev = np.array(jax.devices()).reshape(2, half)
mesh = Mesh(dev, ("proc", "loc"))

def timed_psum(nelem, iters=6):
    x = jnp.ones((2, half, nelem), jnp.float32)
    x = jax.device_put(x, NamedSharding(mesh, P("proc", "loc")))
    f = jax.jit(jax.shard_map(lambda a: lax.psum(a, "proc"), mesh=mesh,
                              in_specs=P("proc", "loc"),
                              out_specs=P(None, "loc"), check_vma=False))
    y = f(x); y.block_until_ready()          # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        y = f(x)
    y.block_until_ready()
    return (time.perf_counter() - t0) / iters

v1, v2 = 1 << 18, 1 << 22                     # 1 MB and 16 MB per device
t1, t2 = timed_psum(v1), timed_psum(v2)
b1, b2 = 4.0 * v1, 4.0 * v2
bw = (b2 - b1) / max(t2 - t1, 1e-9)
lat = max((t1 - b1 / bw) / 2.0, 0.0)
if pid == 0:
    print("PROBE " + json.dumps({
        "t1_s": t1, "t2_s": t2, "bytes1": b1, "bytes2": b2,
        "dcn_bandwidth": bw, "dcn_latency": lat}), flush=True)
''')


def measure(half_devices: int = 4, timeout: float = 420.0) -> dict:
    """Run the 2-process probe; returns the fitted constants."""
    with socket.socket() as s:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind(("localhost", 0))
        port = str(s.getsockname()[1])
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    procs = [subprocess.Popen(
        [sys.executable, "-c", _WORKER, str(i), port, str(half_devices)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=repo) for i in range(2)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    for i, (p, out) in enumerate(zip(procs, outs)):
        if p.returncode != 0:
            raise RuntimeError(f"probe worker {i} failed:\n{out[-2000:]}")
    for out in outs:
        for line in out.splitlines():
            if line.startswith("PROBE "):
                return json.loads(line[len("PROBE "):])
    raise RuntimeError(f"probe printed no result:\n{outs[0][-1000:]}")


def main(argv=None):
    from flexflow_tpu.utils.flags import flag_stream

    args = list(sys.argv[1:] if argv is None else argv)
    out_path = ""
    half = 4
    for a, val in flag_stream(args):
        if a in ("-o", "--out"):
            out_path = val()
        elif a == "--half-devices":
            half = int(val())
    res = measure(half_devices=half)
    artifact = {
        "what": ("measured cross-process (DCN-tier) all-reduce constants "
                 "of the 2-process rig (gloo transport) that executes "
                 "and audits the two-tier plans; fitted to the "
                 "simulator's hierarchical all-reduce cross term "
                 "t = v/bw + 2*lat (sim/collectives._allreduce, G=2)"),
        "protocol": (f"2 procs x {half} virtual devices, psum over the "
                     f"process axis at 1 MB and 16 MB per device, "
                     f"6 timed iters after warmup; slope -> bandwidth, "
                     f"intercept -> latency"),
        **res,
    }
    print(json.dumps({k: artifact[k] for k in
                      ("dcn_bandwidth", "dcn_latency", "t1_s", "t2_s")}))
    if out_path:
        with open(out_path, "w") as f:
            json.dump(artifact, f, indent=2)
        print(f"written to {out_path}")


if __name__ == "__main__":
    main()
