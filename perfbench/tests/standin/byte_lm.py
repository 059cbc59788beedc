"""A stand-in language model for the token entry's tests, never a
configuration: the byte-level toy the program's generative lane serves
(``runtime/decode.py``: learned positions, RMS norms, ``n_layers`` blocks of
causal attention and a relu MLP, tied embeddings), written plainly.  The
weights are drawn as the program draws its own, draw for draw, so that one
seed gives both the same model; ``tests/test_generate_entry.py`` holds the
program's lane to ``forward`` on the CPU.

Two things live here.  ``weights`` and ``forward`` are the family's plain
reference, as ``children/reference_stream.py`` asks for them: one causal
full forward in float32 at ``highest`` precision, no cache.  ``prefill``
and ``decode_step`` are what ``lane_server.py`` serves: the same
mathematics through a key/value cache at the device's default precision.
"""

import json
import math
import os

import jax
import jax.numpy as jnp


def build_params(seed: int, config: dict) -> dict:
    d, n_layers, vocab = config["d_model"], config["n_layers"], config["vocab_size"]
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 4 + 6 * n_layers))

    def mat(shape, scale):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    ones = jnp.ones((d,), jnp.float32)
    params = {"embed": mat((vocab, d), 0.05), "pos": mat((4096, d), 0.02), "ln_f": ones,
              "layers": []}
    for _ in range(n_layers):
        params["layers"].append({
            "ln1": ones, "wqkv": mat((d, 3 * d), 1.0 / math.sqrt(d)),
            "wo": mat((d, d), 1.0 / math.sqrt(d)), "ln2": ones,
            "w1": mat((d, 4 * d), 1.0 / math.sqrt(d)),
            "w2": mat((4 * d, d), 0.5 / math.sqrt(d))})
    return params


def weights(config: dict, seed: int, artifact_dir: str) -> dict:
    """From the seed alone: the artifact holds nothing but that number."""
    with open(os.path.join(artifact_dir, config["served_name"], "lane.json")) as f:
        assert json.load(f)["seed"] == seed
    return build_params(seed, config)


def _rms(x, scale):
    return x * scale / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6)


def _heads(layer, x, n_heads):
    q, k, v = jnp.split(_rms(x, layer["ln1"]) @ layer["wqkv"], 3, axis=-1)
    shape = (*x.shape[:-1], n_heads, x.shape[-1] // n_heads)
    return q.reshape(shape), k.reshape(shape), v.reshape(shape)


def _attend(q, k, v, mask):
    """q [T, H, Dh] over k, v [S, H, Dh]; mask [T, S]."""
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(q.shape[-1])
    w = jax.nn.softmax(jnp.where(mask[None], scores, -1e9), axis=-1)
    return jnp.einsum("hqk,khd->qhd", w, v).reshape(q.shape[0], -1)


def _mlp(layer, x):
    return jax.nn.relu(_rms(x, layer["ln2"]) @ layer["w1"]) @ layer["w2"]


def forward(params: dict, ids, config: dict):
    """Logits [T, vocab] of a causal full forward over ``ids`` [T]."""
    with jax.default_matmul_precision("highest"):
        t = ids.shape[0]
        x = params["embed"][ids] + params["pos"][:t]
        causal = jnp.tril(jnp.ones((t, t), bool))
        for layer in params["layers"]:
            q, k, v = _heads(layer, x, config["n_heads"])
            x = x + _attend(q, k, v, causal) @ layer["wo"]
            x = x + _mlp(layer, x)
        return _rms(x, params["ln_f"]) @ params["embed"].T


# --- the served path: prefill, then a token a step through the cache --------------


def prefill(params: dict, ids, length, config: dict, context: int):
    """``ids`` [bucket], ``length`` of them true.  Returns the cache
    ``[layers, 2, context, heads, head_dim]`` and the last true position's
    logits."""
    t = ids.shape[0]
    x = params["embed"][ids] + params["pos"][:t]
    pos = jnp.arange(t)
    mask = (pos[None, :] <= pos[:, None]) & (pos[None, :] < length)
    cache = []
    for layer in params["layers"]:
        q, k, v = _heads(layer, x, config["n_heads"])
        pad = ((0, context - t), (0, 0), (0, 0))
        cache.append(jnp.stack([jnp.pad(k, pad), jnp.pad(v, pad)]))
        x = x + _attend(q, k, v, mask) @ layer["wo"]
        x = x + _mlp(layer, x)
    return jnp.stack(cache), _rms(x[length - 1], params["ln_f"]) @ params["embed"].T


def decode_step(params: dict, cache, position, token, config: dict):
    """One token at ``position`` through the cache.  Returns (cache, logits)."""
    x = (params["embed"][token] + params["pos"][position])[None]
    mask = (jnp.arange(cache.shape[2]) <= position)[None]
    for i, layer in enumerate(params["layers"]):
        q, k, v = _heads(layer, x, config["n_heads"])
        cache = cache.at[i, 0, position].set(k[0]).at[i, 1, position].set(v[0])
        x = x + _attend(q, cache[i, 0], cache[i, 1], mask) @ layer["wo"]
        x = x + _mlp(layer, x)
    return cache, _rms(x[0], params["ln_f"]) @ params["embed"].T
