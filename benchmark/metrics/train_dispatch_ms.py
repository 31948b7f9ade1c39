"""What the host does for one step (batch to the device, dispatch): median of
``llm.train.step`` over the first ``HEAD`` steps of the measured ``train()``
call.

Only the head of a call shows it. The runtime lets a bounded number of steps
be in flight (32 on the TPU v5e: 32 of a call's 96 step spans read 2 ms, the
other 64 one device step each); a ``train()`` call starts with none, because
the call before it ended in ``llm.train.sync``, so its first steps dispatch
without waiting. From the bound on, the span holds the wait for the device,
which is ``train_step_device_ms``'s to report, not this metric's.
"""

import program_spans as ps

HEAD = 16  # half the runtime's bound: a median over these has no waiting step in it


def value(run):
    head = [s["dur_s"] for s in ps.spans(run, "llm.train.step") if s["attrs"].get("step", HEAD) < HEAD]
    return ps.percentile_ms(head, 50.0)


read = ps.chip_only(value)
