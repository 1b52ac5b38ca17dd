"""The untied reference against the tied one at a tiny size on the CPU: with
the head set to the embedding (transposed, with the embedding's scale) its
loss equals `dense_decoder`'s, and its gradients do too once the head's are
added to the embedding's, as a tied head shares them."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import traffic, weights
from bench.harness.spec import load_module

C = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
     "intermediate_size": 128, "vocab_size": 512, "num_hidden_layers": 2,
     "rope_theta": 1e7,
     "quant": {"w_bits": 4, "a_bits": 4, "edge_bits": 8}}
SHAPES = {"embed.w": (512, 64), "embed.w_scale": (),
          "final_norm.g": (64,), "lm_head.a_scale": (),
          "lm_head.a_offset": (), "layers.ln1.g": (2, 64),
          "layers.ln2.g": (2, 64)}
for name, shape, scale in (("wq", (64, 4, 16), (1, 4, 1)),
                           ("wk", (64, 2, 16), (1, 2, 1)),
                           ("wv", (64, 2, 16), (1, 2, 1)),
                           ("wo", (4, 16, 64), (4, 1, 1)),
                           ("w_gate", (64, 128), ()),
                           ("w_in", (64, 128), ()),
                           ("w_out", (128, 64), ())):
    SHAPES[f"layers.{name}.w"] = (2, *shape)
    SHAPES[f"layers.{name}.w_scale"] = (2, *scale) if scale else (2,)
    SHAPES[f"layers.{name}.a_scale"] = (2,)
    SHAPES[f"layers.{name}.a_offset"] = (2,)


@pytest.fixture(scope="module")
def both():
    tied = load_module("reference", "dense_decoder")
    untied = load_module("reference", "dense_decoder_untied")
    p = jax.jit(lambda k: weights.qat_params(SHAPES, C["quant"], k))(
        weights.seed_key(7))
    pu = dict(p, **{"lm_head.w": p["embed.w"].T,
                    "lm_head.w_scale": p["embed.w_scale"]})
    b = traffic.lm_batch({"mult": 31, "add": 17, "p_noise": 0.1}, 7, 0, 2,
                         16, C["vocab_size"])
    idx, kp = traffic.kd_labels(b["labels"], C["vocab_size"], 16, 0)
    batch = {"tokens": jnp.asarray(b["tokens"]), "kd_idx": jnp.asarray(idx),
             "kd_p": jnp.asarray(kp)}
    grad = lambda mod, params: jax.jit(jax.value_and_grad(
        lambda q: mod.qat_loss(q, batch, C, "f32")))(params)
    return grad(tied, p), grad(untied, pu)


def test_the_loss_equals_the_tied_one(both):
    (lt, _), (lu, _) = both
    assert float(lu) == pytest.approx(float(lt), rel=1e-6)


def test_the_gradients_equal_the_tied_ones(both):
    (_, gt), (_, gu) = both
    shared = {"embed.w": gu["embed.w"] + gu["lm_head.w"].T,
              "embed.w_scale": gu["embed.w_scale"] + gu["lm_head.w_scale"]}
    for k, want in gt.items():
        got = shared.get(k, gu[k])
        # the shared scale's gradient is a float32 sum of rounding residuals
        # of both signs, which cancel; the untied sums it in two parts, the
        # tied in one, and the two differ by 1.3e-3 here (all else < 1e-5)
        rtol = 1e-2 if k == "embed.w_scale" else 1e-5
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=rtol, atol=1e-6 * float(
                                       jnp.max(jnp.abs(want))), err_msg=k)
