"""Share of the train step's device time under the scope `loss_head`
(the blockwise loss with the head matmul, forward and backward,
`models/llama.py::loss_fn`): device seconds of the operations under it
over the device seconds of the `jit_train_step` executions of the
traced window."""
import program_spans as PS


def read(run):
    return PS.scope_share(run, "jit_train_step", "loss_head")
