"""One rehearsal run in a process of its own, with the CPU admitted the
way ``test_rehearsal.py`` admits it: ``python3 rehearse.py <args of
perfbench.run>``. For what must be set before JAX starts, such as four
virtual devices."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from perfbench import common, run, yardstick  # noqa: E402

CANNED_TRACE = {"window_s": 1.0, "busy_s": 0.5, "idle_share": 0.5,
                "exposed_collective_s": 0.1, "op_self_s": {"x": 0.5},
                "device_ops": [["x", 0.5]], "idle_gaps": [["y", 0.5]]}


def admit_cpu(setattr_) -> None:
    """The one place a CPU is admitted: the device check, the peaks
    table and the profiler's trace (a CPU trace has no device plane)
    are replaced through ``setattr_(object, name, value)``."""
    setattr_(run, "require_device", lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": chips})
    setattr_(yardstick, "peaks_for",
             lambda kind: {"bf16_flops_per_s": 1e12})

    def traced(ctx, body):
        body()
        return dict(CANNED_TRACE)
    setattr_(common, "traced", traced)


if __name__ == "__main__":
    admit_cpu(setattr)
    sys.exit(run.main(sys.argv[1:], root=os.path.join(HERE, "data", "tiny")))
