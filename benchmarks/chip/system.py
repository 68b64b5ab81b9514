"""Builds the system under test from a configuration file.

The configuration file holds the published sizes under their published
names; this module maps them onto the program's ``ModelConfig``,
``RunConfig`` and ``ParallelContext`` and builds the model and its mesh
through the program's own entry points, as its launchers do.
"""
from __future__ import annotations

from .reference import Arch


def model_config(cfg: dict):
    from repro.configs.base import ModelConfig
    m = cfg["model"]
    if m.get("tie_word_embeddings"):
        raise ValueError("the system trains an untied head; the config "
                         "must say tie_word_embeddings: false")
    if m.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {m['hidden_act']!r} not wired")
    a = Arch.from_config(cfg)
    return ModelConfig(
        name=cfg["name"], family="dense", num_layers=a.layers,
        d_model=a.hidden, num_heads=a.heads, num_kv_heads=a.kv_heads,
        d_ff=a.ffn, vocab_size=a.vocab, head_dim=a.head_dim,
        mlp_act="silu", mlp_glu=True, rope_theta=a.rope_theta,
        norm_eps=a.eps)


def run_config(cfg: dict, role: str):
    """RunConfig for ``role`` ("train" or "serve"): the role's dtypes, the
    config's run options and optimizer settings."""
    from repro.configs.base import RunConfig
    opt = cfg["optimizer"]
    return RunConfig(**cfg["dtypes"][role], **cfg["run"], lr=opt["lr"],
                     weight_decay=opt["weight_decay"],
                     grad_clip=opt["grad_clip"])


def build(cfg: dict, role: str, devices):
    """(model, mesh) on ``devices`` with the config's layout."""
    from repro.core.api import ParallelContext
    from repro.core.mesh import logical_mesh
    from repro.models.registry import build_model
    lay = cfg["layout"]
    n = lay["depth"] * lay["rows"] * lay["cols"]
    if len(devices) != n:
        raise ValueError(f"layout {lay} needs {n} devices, "
                         f"given {len(devices)}")
    run = run_config(cfg, role)
    ctx = ParallelContext(mode=lay["mode"], data=1, depth=lay["depth"],
                          rows=lay["rows"], cols=lay["cols"],
                          attn_impl=run.attn_impl)
    return build_model(model_config(cfg), ctx, run), \
        logical_mesh(ctx, devices)
