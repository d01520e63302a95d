"""The device's side of a few calls, for the ``*_bench.py`` scripts here:
profile ``work()`` and read the operations the chip ran. Imported from the
script's own directory, with the checkout's ``benchmark`` on ``sys.path``
(every script here puts it there) for the harness's trace reader."""

import glob
import os
import shutil
import tempfile

import jax


def device_events(work):
    """``{device: [(start ns, end ns, operation, scope), ...]}`` as
    ``harness.trace_reduce.read_xplane`` gives them, of a profile of
    ``work()``; what ``work`` returns is waited for inside the profile."""
    from harness import trace_reduce
    trace_dir = tempfile.mkdtemp(prefix="bench_profile_")
    try:
        jax.profiler.start_trace(trace_dir)
        jax.block_until_ready(work())
        jax.profiler.stop_trace()
        path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                                recursive=True))[-1]
        devices, _, _ = trace_reduce.read_xplane(path)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    return devices


def us_a_call(devices, calls):
    """{operation: device us a call} over a profile of ``calls`` calls."""
    per = {}
    for events in devices.values():
        for start, end, op, _ in events:
            per[op] = per.get(op, 0.0) + (end - start) / calls / 1e3
    return per
