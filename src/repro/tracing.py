"""Host spans of the trainer loop and the serving engine, and the named
scopes of the model.

A span is a `jax.profiler.TraceAnnotation`: it records only while a profiler
session runs (`jax.profiler.trace(dir)`), into that session's trace and on
the device trace's clock; otherwise it costs one check. A scope is a
`jax.named_scope`: the op's name stack in the HLO metadata, which xprof
shows and groups by; free at run time.

| Span or scope | In | Covers | Read by |
| --- | --- | --- | --- |
| `train.step` | `run_training` | one loop iteration (`step_num`; the mesh's `data`, `model` and the resolved `fused_matmul`) | xprof steps, `host_syncs.train` |
| `train.hook` | `run_training` | the `on_step` hook | breakdown |
| `train.input` | `run_training` | `sample_batch`, KD labels | `train_input_idle_ms` |
| `train.dispatch` | `run_training` | the `step_fn` call | `host_syncs.train` |
| `train.sync` | `run_training` | one device-to-host read | `host_syncs.train` |
| `train.save` | `run_training` | straggler tick, checkpoint, preemption | breakdown |
| `serve.step` | `ServeEngine.step` | one iteration (`queue`, `active`, `pending`) | `host_syncs.serve` |
| `serve.schedule` | `ServeEngine.step` | expiry, deadlines, admission | breakdown |
| `serve.prefill` | `ServeEngine.step` | one prompt chunk (`rid`, `start`) | breakdown |
| `serve.decode` | `ServeEngine.step` | the pooled decode (`active`) | `scan_copy_ms` |
| `serve.sample` | `ServeEngine.step` | health checks, sampling, finish checks | breakdown |
| `serve.finish` | `ServeEngine._finish` | a request's end, its slot's reset (`rid`) | breakdown |
| `serve.sync` | `ModelExecutor` | the logits' copy to the host | `host_syncs.serve` |
| scope `lm_head` | `forward`, `prefill_step` | the vocabulary projection | xprof |
| scope `layer_scan` | `prefill_step` | the scan over layer groups | xprof |

Metric names are the benchmark's (`bench/metrics/<name>.py`); "breakdown"
is its attribution of idle device time to the innermost host span. Stats
are ints or short strings already at hand: no array is read for a span.
"""
from __future__ import annotations

import jax

TRAIN_STEP = "train.step"
TRAIN_HOOK = "train.hook"
TRAIN_INPUT = "train.input"
TRAIN_DISPATCH = "train.dispatch"
TRAIN_SYNC = "train.sync"
TRAIN_SAVE = "train.save"

SERVE_STEP = "serve.step"
SERVE_SCHEDULE = "serve.schedule"
SERVE_PREFILL = "serve.prefill"
SERVE_DECODE = "serve.decode"
SERVE_SAMPLE = "serve.sample"
SERVE_FINISH = "serve.finish"
SERVE_SYNC = "serve.sync"

LM_HEAD = "lm_head"
LAYER_SCAN = "layer_scan"


def span(name: str, **stats):
    """A host span named `name` carrying `stats` (ints or short strings)."""
    return jax.profiler.TraceAnnotation(name, **stats)
