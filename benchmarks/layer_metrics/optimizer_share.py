"""Share of the train step's device time under the scope `optimizer`
(the optax update and `apply_updates`, `parallel/train_step.py`): device
seconds of the operations under it over the device seconds of the
`jit_train_step` executions of the traced window."""
import program_spans as PS


def read(run):
    return PS.scope_share(run, "jit_train_step", "optimizer")
