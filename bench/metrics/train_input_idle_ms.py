"""Device idle time while the trainer loop builds a step's input: ms in
which no op ran inside the program's `train.input` spans (`sample_batch`,
the KD labels and their transfers), per span, one a step. Layer: trainer
loop, input (launch/train.py:run_training)."""
from bench.harness import layers


def read(run):
    if run.trace is None:
        return None
    spans = layers.windowed(run.trace, layers.TRAIN_INPUT)
    if not spans:
        return None
    return 1e3 * layers.idle_s_in(run.trace, spans) / len(spans)
