"""Host syncs a serving step: the program's `serve.sync` spans (the logits'
copy to the host, one per decode and one per prompt chunk) per `serve.step`
span in the window. Layer: serving engine, host (serve/engine.py)."""
from bench.harness import layers
from bench.harness.trace import inside


def read(run):
    if run.trace is None:
        return None
    steps = layers.windowed(run.trace, layers.SERVE_STEP)
    if not steps:
        return None
    return len(inside(run.trace.spans(layers.SERVE_SYNC), steps)) / len(steps)
