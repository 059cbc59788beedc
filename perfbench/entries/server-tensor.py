"""The model server's tensor entry: ``POST /v1/models/<name>:predict``, a
msgpack uint8 tensor of model-sized pictures a request, from a closed loop
of callers (``traffic.ServerTensor``, ``traffic.run_closed``).  Gateway,
decode and coalescing are bypassed.
"""

from perfbench import pictures, traffic
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

GENERATORS = ("closed",)


def make_inputs(run) -> None:
    """The traffic's pictures, and what the reference will read."""
    mix, shape = run.mix, tuple(run.config["input_shape"])
    n, per = int(mix["pool"]), int(mix["images_per_request"])
    pool = pictures.tensor_pool(run.seed, n, shape)
    run.pool_size = n
    run.reference_inputs = ["--tensor-pool", str(n)]
    rows = traffic.balanced_rows(run.seed, n, per * int(mix["bodies"]))
    run.body_rows = [tuple(int(r) for r in rows[i * per:(i + 1) * per])
                     for i in range(int(mix["bodies"]))]
    run.bodies = [traffic.encode_tensor_body(pool[list(r)]) for r in run.body_rows]


def boot_front(run) -> None:
    """Nothing stands in front of the model server."""


def stop_front(run) -> None:
    pass


def drive(run, on_window_start) -> None:
    entry = traffic.ServerTensor(run.server, run.model, run.bodies)
    run.outcomes, run.t_zero = traffic.run_closed(
        entry, run.mix, run.seed, float(run.mix["lead_in_s"]), run.seconds,
        run.body_rows, on_window_start)
    # those answered after the window's start, whenever they were sent
    run.window = [o for o in run.outcomes if o.done_s >= 0]
