"""Per-architecture smoke tests: every assigned arch instantiates a REDUCED
config, runs one forward + one train step on CPU, asserts shapes + no NaNs."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import ARCHS
from repro.data import make_lm_batch
from repro.models import lm, transformer as T
from repro.optim import adamw

ALL_ARCHS = sorted(ARCHS)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_forward_shapes_no_nan(arch):
    cfg = ARCHS[arch].reduced()
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    batch = lm.smoke_batch(cfg, batch=2, seq=16)
    hidden = T.forward(params, cfg, batch["tokens"],
                       frontend_embeds=batch.get("frontend_embeds"),
                       encoder_embeds=batch.get("encoder_embeds"))
    fe = cfg.n_frontend_tokens if (cfg.frontend and not cfg.is_encoder_decoder) else 0
    assert hidden.shape == (2, 16 + fe, cfg.d_model)
    assert not bool(jnp.isnan(hidden.astype(jnp.float32)).any())
    logits = T.logits_from_hidden(params, cfg, hidden)
    assert logits.shape[-1] == cfg.padded_vocab


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_one_train_step(arch):
    cfg = ARCHS[arch].reduced()
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    opt = adamw(1e-3)
    step = jax.jit(lm.make_train_step(cfg, opt))
    state = {"params": params, "opt": opt.init(params),
             "step": jnp.zeros((), jnp.int32)}
    batch = make_lm_batch(cfg, 0, batch=2, seq=17)
    state, metrics = step(state, batch)
    loss = float(metrics["loss"])
    assert loss == loss, "loss is NaN"          # NaN check
    assert 0.0 < loss < 20.0
    assert int(state["step"]) == 1


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "rwkv6-3b",
                                  "mixtral-8x7b", "jamba-1.5-large-398b"])
def test_loss_decreases(arch):
    cfg = ARCHS[arch].reduced()
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    opt = adamw(3e-3)
    step = jax.jit(lm.make_train_step(cfg, opt))
    state = {"params": params, "opt": opt.init(params),
             "step": jnp.zeros((), jnp.int32)}
    losses = []
    for s in range(12):
        batch = make_lm_batch(cfg, s, batch=4, seq=33)
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert min(losses[-3:]) < losses[0], losses


def test_param_counts_in_expected_range():
    """Full-config param counts must be in the ballpark of the arch names."""
    expectations = {
        "command-r-plus-104b": (90e9, 130e9),
        "deepseek-7b": (5e9, 9e9),
        "internlm2-1.8b": (1.2e9, 2.5e9),
        "mixtral-8x7b": (40e9, 55e9),
        "llama4-maverick-400b-a17b": (330e9, 480e9),
        "jamba-1.5-large-398b": (300e9, 480e9),
        "rwkv6-3b": (2e9, 4.5e9),
        "whisper-base": (0.04e9, 0.2e9),
    }
    for arch, (lo, hi) in expectations.items():
        n = ARCHS[arch].param_count()
        assert lo <= n <= hi, f"{arch}: {n/1e9:.1f}B not in [{lo/1e9},{hi/1e9}]"


def test_moe_active_params_smaller():
    for arch in ("mixtral-8x7b", "llama4-maverick-400b-a17b",
                 "jamba-1.5-large-398b"):
        cfg = ARCHS[arch]
        assert cfg.param_count(active_only=True) < 0.55 * cfg.param_count()


def test_sub_quadratic_flags():
    """long_500k applies only to sub-quadratic architectures."""
    expect_subq = {"rwkv6-3b", "jamba-1.5-large-398b", "h2o-danube-3-4b",
                   "mixtral-8x7b"}
    for name, cfg in ARCHS.items():
        assert cfg.sub_quadratic == (name in expect_subq), name
