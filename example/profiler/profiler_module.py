"""Per-op device profiling of a fused training step (reference
example/profiler/*: profiler_executor.py / profiler_matmul.py).

Trains a small CNN for a few fused steps under mx.profiler mode='all_xla',
then prints mx.profiler.dumps(): per-graph-node device times — forward
rows under the layer name, backward rows as _backward_<name>, the
trainer's own work as step.update / step.guard / ..., exactly the
reference's per-op profile table (src/engine/profiler.cc) but over a FUSED
XLA program.  A TPU trace names device events by HLO instruction; the
graph node is the instruction's op_name in the compiled step's text, which
the trainer hands over (``step_text()``).

Device-op events need a real accelerator backend; on cpu the script
still writes the host-engine Chrome trace (profile.json).
"""
import logging
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", ".."))

import mxnet_tpu as mx
from mxnet_tpu import profiler
from mxnet_tpu.parallel import SPMDTrainer


def main(steps=3, out_dir="/tmp/mxtpu_profile"):
    import jax
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, num_filter=16, kernel=(3, 3),
                             pad=(1, 1), name="conv1")
    net = mx.sym.Activation(net, act_type="relu", name="relu1")
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2),
                         pool_type="max", name="pool1")
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=10,
                                name="fc1")
    net = mx.sym.SoftmaxOutput(net, name="softmax")

    batch = (np.random.rand(32, 3, 24, 24).astype("f"),
             np.random.randint(0, 10, 32).astype("f"))
    trainer = SPMDTrainer(net, "sgd", {"learning_rate": 0.1,
                                       "rescale_grad": 1.0 / 32})
    trainer.bind([("data", (32, 3, 24, 24))], [("softmax_label", (32,))])
    trainer.init_params(mx.initializer.Xavier())
    trainer.step(*batch)              # compile outside the trace

    profiler.profiler_set_config(
        mode="all_xla", filename=os.path.join(out_dir, "profile.json"),
        trace_dir=os.path.join(out_dir, "xla"))
    profiler.profiler_set_state("run")
    for _ in range(steps):
        outs = trainer.step(*batch)
    jax.block_until_ready(outs)
    profiler.profiler_set_state("stop")

    os.makedirs(out_dir, exist_ok=True)
    profiler.dump_profile()           # host-engine Chrome trace
    if jax.default_backend() == "cpu":
        print("cpu backend: no device-op events; host trace written to",
              os.path.join(out_dir, "profile.json"))
        return None
    table = profiler.dumps(trace_dir=os.path.join(out_dir, "xla"),
                           hlo_text=trainer.step_text())
    print(table)
    return table


if __name__ == "__main__":
    logging.basicConfig(level=logging.INFO)
    main()
