"""Host syncs a training step: the program's `train.sync` spans (each a
device-to-host read on the step path) per `train.step` span that dispatched
a step, in the window. Layer: trainer loop (launch/train.py:run_training)."""
from bench.harness import layers
from bench.harness.trace import inside


def read(run):
    if run.trace is None:
        return None
    tr = run.trace
    steps = [s for s in layers.windowed(tr, layers.TRAIN_STEP)
             if inside(tr.spans(layers.TRAIN_DISPATCH), [s])]
    if not steps:
        return None
    return len(inside(tr.spans(layers.TRAIN_SYNC), steps)) / len(steps)
