#!/usr/bin/env python3
"""What the decode loop's own account of its time costs a read (PR 37).

    python3 exp/loop_cost.py [--seconds 8] [--rounds 3] [--slots 128] [--out F.json]

Two readings, both in one process on whatever device JAX finds (the chip,
through the chip tool; ``--tiny`` on the CPU here to rehearse):

- ``micro``: one read's worth of ``runtime.decode.LoopClock`` calls -- six
  phase boundaries with ``jax.profiler.TraceAnnotation`` as the annotate
  factory (no profile running), the ``is_ready`` probes on a device array,
  one dispatch and one publish into real counters -- timed over many
  rounds, against the same calls on a clock that does nothing.  The array
  is ready, so every read books a dry-up: the dearest path;
- ``lane``: the lane's own loop (the toy decoder at ``--slots`` slots, every
  slot's stream resubmitted as it ends, by one drain thread a stream as the
  transport threads are) for ``--seconds``, in turns with the real clock
  and with the stub in its place (``decode.LoopClock`` swapped; ABBA, so a
  drift of the machine falls on both): the loop's milliseconds a read, and
  the difference of each pair.  The toy's step is short, so the loop is the
  host's and a cost per read shows whole.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


class NullClock:
    """The instrumentation stubbed out: the loop's calls, doing nothing."""

    def __init__(self, metrics, annotate, has_work):
        self.born = self.ended = time.perf_counter()

    def enter(self, phase, now=None):
        pass

    def dispatched(self, t, newest):
        pass

    def publish(self):
        pass

    def close(self):
        self.ended = time.perf_counter()


def micro(n: int) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    from kubernetes_deep_learning_tpu.runtime import decode
    from kubernetes_deep_learning_tpu.utils import metrics as metrics_lib

    handle = jnp.zeros((8,), jnp.int32) + 1
    handle.block_until_ready()
    metrics = metrics_lib.decode_metrics(metrics_lib.Registry(), "cost")

    def cycle(clock):
        clock.enter("admit")
        clock.enter("dispatch")
        clock.dispatched(time.perf_counter(), handle)
        clock.enter("flush")
        clock.enter("read")
        clock.enter("book", time.perf_counter())
        clock.publish()

    out = {}
    for name, make in (("stub", NullClock), ("clock", decode.LoopClock)):
        clock = make(metrics, TraceAnnotation, lambda: True)
        for _ in range(n // 10):
            cycle(clock)
        t0 = time.perf_counter()
        for _ in range(n):
            cycle(clock)
        out[name + "_us_a_read"] = 1e6 * (time.perf_counter() - t0) / n
    out["cost_us_a_read"] = out["clock_us_a_read"] - out["stub_us_a_read"]
    t0 = time.perf_counter()
    for _ in range(n):
        handle.is_ready()
    out["is_ready_us"] = 1e6 * (time.perf_counter() - t0) / n
    t0 = time.perf_counter()
    for _ in range(n):
        with TraceAnnotation("decode.loop.book"):
            pass
    out["annotation_us"] = 1e6 * (time.perf_counter() - t0) / n
    out["device"] = str(jax.devices()[0].device_kind)
    return out


def lane(seconds: float, rounds: int, slots: int) -> dict:
    from kubernetes_deep_learning_tpu.runtime import decode
    from kubernetes_deep_learning_tpu.utils import metrics as metrics_lib
    from kubernetes_deep_learning_tpu.utils import trace as trace_lib
    from jax.profiler import TraceAnnotation

    engine = decode.DecodeEngine("cost-lane", max_slots=slots, page_size=16,
                                 max_pages_per_seq=8, prompt_buckets=(16,))
    engine.warmup()
    real = decode.LoopClock
    out = {"stub": [], "clock": []}
    for k in range(rounds):
        for name in (("stub", "clock"), ("clock", "stub"))[k % 2]:
            decode.LoopClock = NullClock if name == "stub" else real
            registry = metrics_lib.Registry()
            tracer = trace_lib.Tracer("model-server", annotate=TraceAnnotation)
            sched = decode.DecodeScheduler(engine, registry=registry, tracer=tracer)
            sched.start()
            stop = time.monotonic() + seconds

            def stream(k):
                while time.monotonic() < stop:
                    gen = sched.submit(None, 96, token_ids=[1 + k % 200] * 12,
                                       ignore_eos=True)
                    for _ev in gen.iter_events(timeout_s=60.0):
                        pass

            threads = [threading.Thread(target=stream, args=(k,)) for k in range(slots)]
            for t in threads:
                t.start()
            time.sleep(2.0)             # every slot filled
            m = metrics_lib.decode_metrics(registry, "cost-lane")
            r0, t0 = m["steps"].value + m["prefill_chunks"].value, time.perf_counter()
            time.sleep(seconds - 3.0)
            r1, t1 = m["steps"].value + m["prefill_chunks"].value, time.perf_counter()
            for t in threads:
                t.join()
            sched.close()
            out[name].append(1000.0 * (t1 - t0) / (r1 - r0))
    decode.LoopClock = real
    med = {k: statistics.median(v) for k, v in out.items()}
    pairs = [1000.0 * (c - s) for c, s in zip(out["clock"], out["stub"])]
    return {"ms_a_read": out, "median_ms_a_read": med, "pair_cost_us": pairs,
            "cost_us_a_read": statistics.median(pairs)}


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seconds", type=float, default=8.0)
    p.add_argument("--rounds", type=int, default=6)
    p.add_argument("--slots", type=int, default=128)
    p.add_argument("--micro", type=int, default=20000)
    p.add_argument("--tiny", action="store_true", help="rehearse on the CPU")
    p.add_argument("--out")
    args = p.parse_args()
    if args.tiny:
        args.seconds, args.rounds, args.slots, args.micro = 4.0, 1, 4, 2000
    result = {"micro": micro(args.micro), "lane": lane(args.seconds, args.rounds, args.slots)}
    text = json.dumps(result, indent=1)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
