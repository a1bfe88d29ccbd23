"""Device milliseconds a traced step spends in instructions of the
optimizer update (scope ff_update) that XLA left outside other fusions:
self time on the ``XLA Ops`` line, mean of the devices.

A fusion is one instruction and is charged whole to the operator and pass
of its root (its own ``op_name``).  On the TPU XLA fuses the optimizer's
``p - lr * v`` into each weight-gradient fusion, whose root is a backward
instruction: that update work reads as "backward", and
``ops.update_ms_per_step`` holds only what stays outside such fusions
(my chip runs, PR 26: 0.014 ms on Inception, 1.36 on GPT-2, 1.24 on
AlexNet).  A change to the optimizer therefore shows in
``ops.backward_ms_per_step`` first; forward and backward carry the same
root-only attribution of fusions that span two operators.
"""

from benchmarks.program_trace import pass_ms_per_step

METRIC = {"name": "ops.update_ms_per_step", "unit": "ms",
          "better": "lower", "source": "device_trace", "layer": "ops",
          "moves": "train_items_per_s_per_chip"}


def read(facts):
    return pass_ms_per_step(facts, "update")
