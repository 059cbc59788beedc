"""The gateway's URL entry: ``POST /predict {"url": ...}`` under open-loop
Poisson arrivals (``traffic.GatewayUrl``, ``traffic.run_open``).  The
pictures are encoded sources on a picture host of the benchmark's own
(``children/image_host.py``); the gateway fetches them and forwards the
bytes to the model server, which decodes, resizes and batches.
"""

import os

from perfbench import pictures, procs, traffic
from perfbench.image_serving import (  # noqa: F401 - the entry's interface
    QUANTITIES,
    REFERENCE_OUT,
    check_status,
    compare,
    quantities,
    reference_args,
    server_args,
    warming,
)

GENERATORS = ("open-poisson",)
PACKAGE = "kubernetes_deep_learning_tpu"


def make_inputs(run) -> None:
    """The traffic's pictures, and what the reference will read."""
    mix = run.mix
    pool_dir = os.path.join(run.work, "pool")
    os.makedirs(pool_dir)
    pool = pictures.encoded_pool(run.seed, mix["pictures"])
    for i, (fmt, data) in enumerate(pool):
        with open(os.path.join(pool_dir, f"{i:04d}.{fmt}"), "wb") as f:
            f.write(data)
    run.pool_size = len(pool)
    run.reference_inputs = ["--inputs", pool_dir]
    port = procs.free_port()
    run.host_proc = run.children.spawn(
        "image_host", [run.child_script("image_host.py"), pool_dir, str(port)],
        env=run.host_env)
    run.image_host = f"http://127.0.0.1:{port}"


def boot_front(run) -> None:
    """The gateway, with default flags, in front of the model server."""
    port = procs.free_port()
    run.gateway_proc = run.children.spawn("gateway", [
        "-m", f"{PACKAGE}.serving.gateway", "--serving-host",
        run.server.split("//")[1], "--port", str(port), "--model", run.model,
    ], env=run.host_env)
    run.gateway = run.tiers["gateway"] = f"http://127.0.0.1:{port}"
    procs.wait_ready(run.children, "gateway", run.gateway_proc, run.gateway)


def stop_front(run) -> None:
    run.children.stop("gateway", run.gateway_proc)
    run.children.stop("image_host", run.host_proc)


def drive(run, on_window_start) -> None:
    entry = traffic.GatewayUrl(run.gateway, run.image_host, run.labels, run.seed)
    run.outcomes, run.t_zero = traffic.run_open(
        entry, run.mix, run.seed, float(run.mix["lead_in_s"]), run.seconds,
        run.pool_size, on_window_start)
    run.window = [o for o in run.outcomes if o.due_s >= 0]   # the requests due in it
