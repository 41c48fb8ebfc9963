"""The general traffic generator: a federated job's data from the
parameters of a ``traffic/<name>.json`` file and the run's seed.

A traffic file has three parts:

  backend   "pod" (the sharded backend, ``repro.fl.pod``) or "host" (the
            single-program backend, ``repro.fl.simulation``)
  data      the population: "tokens" (next-token sequences) or "images"
            (a labelled image classification set split non-IID)
  fl        the federated job: clients per round, local steps, batch,
            learning rate, rounds per dispatch, evaluation cadence

Every array is drawn from the seed, in bulk and on the device; the same
seed gives the same data.  Labels and the Dirichlet split of an image
set are drawn on the host, since they are small.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import numpy as np


@dataclasses.dataclass
class Population:
    x: object                # (clients, pool, ...) on the device
    y: object                # (clients, pool, ...) on the device
    n_real: np.ndarray       # (clients,) FedAvg weights
    test_x: np.ndarray       # host
    test_y: np.ndarray
    n_classes: int


def clients_per_round(t: dict) -> int:
    """K, as the traffic states it: a count on the pod backend, a share
    of the population on the host backend."""
    fl, n = t["fl"], t["data"]["clients"]
    if t["backend"] == "pod":
        return max(1, min(fl["clients_per_round"], n))
    return max(1, int(round(fl["participation"] * n)))


def samples_per_round(t: dict) -> int:
    fl = t["fl"]
    return clients_per_round(t) * fl["local_steps"] * fl["batch_size"]


def make(t: dict, seed: int, key) -> Population:
    kind = t["data"]["kind"]
    if kind == "tokens":
        return _tokens(t["data"], seed, key)
    if kind == "images":
        return _images(t["data"], seed, key)
    raise ValueError(f"unknown data kind {kind!r}")


def _tokens(d: dict, seed: int, key) -> Population:
    """Next-token sequences.  Each of ``topics`` topics ranks the
    vocabulary in its own random order and draws tokens from a Zipf
    law (exponent ``zipf``) over that order; each client mixes the
    topics with Dirichlet(``beta``) weights, and each of its sequences
    follows one topic drawn from the mix."""
    import jax
    import jax.numpy as jnp

    n, pool, S, V, T = (d["clients"], d["sequences_per_client"],
                        d["seq_len"], d["vocab"], d["topics"])
    rng = np.random.default_rng([seed, 1])
    mix = rng.dirichlet(np.full(T, d["beta"]), size=n)          # (n, T)

    @jax.jit
    def draw(key, mix):
        k_perm, k_topic, k_tok = jax.random.split(key, 3)
        order = jax.vmap(lambda k: jax.random.permutation(k, V))(
            jax.random.split(k_perm, T))                          # (T, V)
        rank_p = 1.0 / jnp.arange(1, V + 1, dtype=jnp.float32) ** d["zipf"]
        cdf = jnp.cumsum(rank_p) / jnp.sum(rank_p)
        topic = jax.random.categorical(k_topic, jnp.log(mix)[:, None, :],
                                       shape=(n, pool))           # (n, pool)
        u = jax.random.uniform(k_tok, (n, pool, S + 1))
        rank = jnp.minimum(jnp.searchsorted(cdf, u), V - 1)
        seqs = order[topic[..., None], rank].astype(jnp.int32)
        return seqs[..., :-1], seqs[..., 1:]

    x, y = draw(key, jnp.asarray(mix, jnp.float32))
    empty = np.zeros((0, S), np.int32)
    return Population(x=x, y=y, n_real=np.full(n, pool, np.int64),
                      test_x=empty, test_y=empty, n_classes=V)


def dirichlet_split(labels: np.ndarray, n: int, beta: float,
                    rng: np.random.Generator, least: int = 2):
    """Per-class Dirichlet(beta) shares of the sample indices over ``n``
    clients (Hsu et al. 2019), drawn again until every client holds at
    least ``least`` samples."""
    classes = np.unique(labels)
    for _ in range(100):
        parts = [[] for _ in range(n)]
        for c in classes:
            idx = np.flatnonzero(labels == c)
            rng.shuffle(idx)
            cuts = (np.cumsum(rng.dirichlet(np.full(n, beta))) *
                    len(idx)).astype(int)[:-1]
            for cid, part in enumerate(np.split(idx, cuts)):
                parts[cid].extend(part.tolist())
        if min(len(p) for p in parts) >= least:
            return [np.array(sorted(p), np.int64) for p in parts]
    raise RuntimeError(f"no Dirichlet({beta}) split over {n} clients gave "
                       f"each {least} samples")


def _images(d: dict, seed: int, key) -> Population:
    """A labelled image set with the sizes of the one it stands for:
    each class has a smooth random template; a sample is its class's
    template, circularly shifted by up to ``max_shift`` pixels, scaled
    by a contrast in [0.7, 1.3] and with Gaussian noise of std
    ``noise``.  The train set is split over the clients by
    :func:`dirichlet_split`; each client's pool is padded to
    ceil(train / clients) rows by drawing again from its own samples,
    and its FedAvg weight is its true count."""
    import jax
    import jax.numpy as jnp

    n, C = d["clients"], d["classes"]
    H, W, ch = d["height"], d["width"], d["channels"]
    rng = np.random.default_rng([seed, 2])
    y_train = rng.integers(0, C, d["train"]).astype(np.int32)
    y_test = rng.integers(0, C, d["test"]).astype(np.int32)
    parts = dirichlet_split(y_train, n, d["beta"], rng)
    pool = max(math.ceil(d["train"] / n), 2)
    rows, n_real = [], []
    for p in parts:
        n_real.append(len(p))
        if len(p) >= pool:
            take = rng.choice(p, size=pool, replace=False)
        else:
            take = np.concatenate([p, rng.choice(p, size=pool - len(p))])
        rng.shuffle(take)
        rows.append(take)
    rows = np.stack(rows)

    def draw(k_tmpl, labels, key):
        coarse = jax.random.normal(k_tmpl, (C, H // 4, W // 4, ch))
        tmpl = jax.image.resize(coarse, (C, H, W, ch), "linear")
        k_s, k_c, k_n = jax.random.split(key, 3)
        m = labels.shape[0]
        sh = jax.random.randint(k_s, (m, 2), -d["max_shift"],
                                d["max_shift"] + 1)
        x = jax.vmap(lambda t, s: jnp.roll(t, (s[0], s[1]), axis=(0, 1)))(
            tmpl[labels], sh)
        contrast = jax.random.uniform(k_c, (m, 1, 1, 1), minval=0.7,
                                      maxval=1.3)
        return x * contrast + d["noise"] * jax.random.normal(k_n, x.shape)

    draw = jax.jit(draw)
    k_tmpl, k_train, k_test = jax.random.split(key, 3)
    # one template set for both splits
    train = draw(k_tmpl, jnp.asarray(y_train), k_train)
    test = draw(k_tmpl, jnp.asarray(y_test), k_test)
    idx = jnp.asarray(rows)
    return Population(x=train[idx], y=jnp.asarray(y_train)[idx],
                      n_real=np.asarray(n_real, np.int64),
                      test_x=np.asarray(test), test_y=y_test, n_classes=C)


def check(t: dict, supported: Optional[dict] = None) -> None:
    """Refuse a traffic file whose ``fl`` part asks for what the plain
    reference does not compute (``supported``: key -> allowed values)."""
    for k, allowed in (supported or {}).items():
        v = t["fl"].get(k)
        if v not in allowed:
            raise ValueError(f"traffic fl.{k}={v!r}: the reference computes "
                             f"only {allowed}")
