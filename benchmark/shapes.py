"""Operations and bytes one D4PG gradient step needs, from a configuration's
sizes alone. These feed ``chunk_roofline``; they live with the benchmark so
that no later PR can move them.

Counting rule. A multiply-add is 2 FLOPs. Only what the algorithm needs is
counted: a layer's weight gradient where its parameters are trained by that
loss, its input gradient where something upstream needs it. So the critic's
first layer gets no input gradient (its input is data), and in the actor
loss the critic contributes input gradients from its head back to the
action and no weight gradients. Counting every backward pass as twice its
forward (the usual shorthand) gives 1.35 GFLOP for humanoid-mlp at batch
256; this rule gives 1.08 GFLOP, which is also what XLA's ``cost_analysis``
of ``make_update`` printed in PR 21 (1.07e9): the compiler skips the same
unneeded products.
"""

from __future__ import annotations


def _conv_out(size: int, stride: int) -> int:
    return -(-size // stride)  # SAME padding


def encoder_layers(cfg_model: dict) -> list[tuple[str, int, int]]:
    """(name, multiply-adds per image, parameters) of the pixel encoder:
    3x3 SAME convs, stride 2 then 1, flatten, projection to the latent."""
    h, w, c = cfg_model["obs_shape"]
    layers = []
    for i, ch in enumerate(cfg_model["encoder_channels"]):
        stride = 2 if i == 0 else 1
        h, w = _conv_out(h, stride), _conv_out(w, stride)
        layers.append((f"conv{i + 1}", h * w * ch * 9 * c, 9 * c * ch + ch))
        c = ch
    latent = cfg_model.get("latent_dim", 50)
    layers.append(("proj", h * w * c * latent, h * w * c * latent + latent))
    layers.append(("ln", 0, 2 * latent))
    return layers


def mlp_layers(in_dim: int, hidden, out_dim: int,
               concat_at: int | None = None, concat_dim: int = 0):
    """(name, multiply-adds per row, parameters) of an MLP; ``concat_at``
    is the layer whose input also takes ``concat_dim`` more columns (the
    critic takes the action at its second layer)."""
    layers, d = [], in_dim
    for i, width in enumerate(list(hidden) + [out_dim]):
        if concat_at is not None and i == concat_at:
            d += concat_dim
        layers.append((f"fc{i + 1}", d * width, d * width + width))
        d = width
    return layers


def step_counts(cfg: dict) -> dict:
    """FLOPs and HBM bytes of one gradient step at the configuration's
    batch size: ``{"flops", "bytes", "flops_shorthand", "params"}``."""
    m, lr = cfg["model"], cfg["learner"]
    b = int(lr["batch_size"])
    pixels = bool(m.get("pixels", False))
    atoms, act = int(m["n_atoms"]), int(m["act_dim"])
    enc = encoder_layers(m) if pixels else []
    feat = m.get("latent_dim", 50) if pixels else int(m["obs_dim"])
    actor = mlp_layers(feat, m["hidden"], act)
    critic = mlp_layers(feat, m["hidden"], atoms, concat_at=1, concat_dim=act)

    def macs(layers):
        return sum(x[1] for x in layers)

    enc_f, a_f, c_f = macs(enc), macs(actor), macs(critic)
    first = (enc[0][1] if pixels else None)
    # forward passes: target actor, target critic, critic, then actor and
    # critic again inside the actor loss; each pixel pass runs the encoder
    fwd = 2 * (enc_f + a_f) + 3 * (enc_f + c_f)
    # critic loss backward: weight gradients everywhere, input gradients
    # everywhere but the very first layer
    c_first = first if pixels else critic[0][1]
    bwd_critic = 2 * (enc_f + c_f) - c_first
    # actor loss backward: the critic passes the gradient from its head
    # back to the action (every layer but its first), the actor takes
    # weight gradients and input gradients down to its own first layer
    # (with a shared encoder the latent is detached there)
    bwd_actor = (c_f - critic[0][1]) + 2 * a_f - actor[0][1]
    if pixels and not m.get("share_encoder", False):
        bwd_actor += 2 * enc_f - first
    proj = atoms * atoms  # the categorical projection's [A, A] contraction
    flops = 2 * b * (fwd + bwd_critic + bwd_actor + proj)
    shorthand = 2 * b * (fwd + 2 * (enc_f + c_f)
                         + 2 * (enc_f + a_f + enc_f + c_f) + proj)

    def n_params(layers):
        return sum(x[2] for x in layers)

    p_actor = n_params(enc) + n_params(actor)
    p_critic = n_params(enc) + n_params(critic)
    # float32 state that every step reads and writes once: parameters, Adam
    # first and second moments, target parameters — for both networks
    state_bytes = 4 * 2 * 4 * (p_actor + p_critic)
    obs_bytes = 1 if pixels else 4
    obs_elems = 1
    for d in (m["obs_shape"] if pixels else [m["obs_dim"]]):
        obs_elems *= int(d)
    row_bytes = 2 * obs_elems * obs_bytes + 4 * (act + 3)
    return {
        "flops": float(flops),
        "flops_shorthand": float(shorthand),
        "bytes": float(state_bytes + b * row_bytes),
        "params": p_actor + p_critic,
        "row_bytes": row_bytes,
    }


def roofline_seconds(counts: dict, peak: dict) -> tuple[float, str]:
    """The least time the chip could take for one step, and which peak
    bounds it (``"flops"`` or ``"bytes"``)."""
    t_flops = counts["flops"] / peak["bf16_flops_per_s"]
    t_bytes = counts["bytes"] / peak["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
