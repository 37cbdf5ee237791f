"""The one general generator: a traffic file's parameters and a seed in, the
clients' batches out. The same seed gives the same arrays; another seed gives
the same sizes with other values.

``federated_rounds`` traffic: ``clients_total`` clients, all of them in every
round, each with an equal share of the rows (shares differ by at most one
row), batched ``[clients, batches, batch_size, ...]`` with a mask on the rows
that pad the last batch.
"""

from __future__ import annotations

import numpy as np


def program_seed(seed):
    """The seed the system is given (it keeps unsigned 32 bits)."""
    return int(seed) % (2 ** 32)


def generate(cfg, traffic, seed):
    if traffic["kind"] != "federated_rounds":
        raise ValueError(f"unknown traffic kind {traffic['kind']!r}")
    if traffic["clients_per_round"] != traffic["clients_total"]:
        raise ValueError("partial participation needs the system's client "
                         "selection stream in the reference; not built yet")
    clients, bs = traffic["clients_total"], traffic["batch_size"]
    inp = cfg["input"]
    if inp["kind"] == "tokens":
        counts = np.full(clients, traffic["rows_per_client"], np.int64)
    else:
        base, rem = divmod(traffic["samples_total"], clients)
        counts = base + (np.arange(clients) < rem).astype(np.int64)
    nb = int(-(-counts.max() // bs))
    if np.any(-(-counts // bs) != nb):
        raise ValueError("clients differ in their number of batches")
    slots = nb * bs
    mask = (np.arange(slots)[None, :] < counts[:, None]).astype(np.float32)
    rng = np.random.default_rng([int(seed), 20240924])
    if inp["kind"] == "tokens":
        seq = traffic["seq_len"]
        tok = rng.integers(0, cfg["vocab_size"], size=(clients, slots, seq + 1),
                           dtype=np.int32)
        x, y = tok[..., :-1], tok[..., 1:]
        x = x * mask[..., None].astype(np.int32)
        y = np.where(mask[..., None] > 0, y, -1).astype(np.int32)
    elif inp["kind"] == "image":
        shape = tuple(cfg["input_shape"])
        classes = cfg["num_classes"]
        templates = (0.5 * rng.standard_normal((classes,) + shape)
                     ).astype(np.float32)
        y = rng.integers(0, classes, size=(clients, slots), dtype=np.int32)
        x = rng.standard_normal((clients, slots) + shape, dtype=np.float32)
        x += templates[y]
        x *= mask.reshape(mask.shape + (1,) * len(shape))
        y = y * mask.astype(np.int32)
    else:
        raise ValueError(f"unknown input kind {inp['kind']!r}")

    def batched(a):
        return np.ascontiguousarray(a.reshape((clients, nb, bs) + a.shape[2:]))

    return {"x": batched(x), "y": batched(y), "mask": batched(mask),
            "num_samples": counts}


def program_batch_orders(seed32, round_idx, clients, batches, epochs):
    """``[clients, epochs * batches]`` int32: the batch each local step of
    each client takes in round ``round_idx``.

    The order is part of what the system promises for a seed (the same seed
    replays the same run), so the reference follows it. It is derived here,
    from ``jax.random`` alone, as the system documents it: the simulator's key
    is the second half of ``split(PRNGKey(seed))``; a round folds in its
    index, a client its id; the first half of the client key's split, with
    the epoch folded in, draws one uniform per batch, and the epoch visits
    its batches in the order that sorts those draws. Every batch is real
    here, so no padded batch is pushed to the end.
    """
    import jax
    import jax.numpy as jnp

    _, sim_key = jax.random.split(jax.random.PRNGKey(seed32))
    round_key = jax.random.fold_in(sim_key, round_idx)

    def client(cid):
        data_key, _ = jax.random.split(jax.random.fold_in(round_key, cid))

        def epoch(e):
            draws = jax.random.uniform(jax.random.fold_in(data_key, e),
                                       (batches,))
            return jnp.argsort(draws)

        return jax.vmap(epoch)(jnp.arange(epochs)).reshape(-1)

    return jax.jit(jax.vmap(client))(jnp.arange(clients)).astype(jnp.int32)
