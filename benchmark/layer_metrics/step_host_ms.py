"""Train step: median duration of the ``train_step.call`` span: the host's
work for one step (flattening, the optimizer state's hand-over, the dispatch,
the write-back of every parameter). While it stays under ``step_device_ms``
the device is fed."""
from benchmark.harness.program_trace import step_host_ms as read  # noqa: F401
