"""The benchmark's weights, made on the device from ``--seed``.

Every parameter is drawn as the program's own initializer draws it
(``references/<family>.py``'s ``init``), then the output head is shaped
as the configuration's ``weights`` group says:

* the columns of the pad and end ids are zero, so those logits are
  exactly 0 at every position; with the others scaled by 6 a row draws
  them under once in 1e10 tokens, so every row runs to its budget and
  every seed does the same work;
* every other column is scaled by ``head_scale``, so the logits have that
  standard deviation (the final norm's output has norm sqrt(d) under a
  gain of 1) and sampling entropy is near a trained policy's;
* the digits' columns (``answer_ids``) are one shared column along the
  mean direction ``u`` of the final hidden state where the policy
  samples (a probe sampled from the seed after a prompt of the cell's
  length), scaled so that a digit is drawn at about ``answer_share`` of
  the positions next to the prompt: rows write numbers, about half the rows of a group earn
  the arithmetic reward, and the train step has a gradient to follow.

The probe is the only part that needs a forward pass; ``regenerate``
rebuilds every leaf from the seed and the probe's three numbers alone,
which is how the parameters' change is measured without holding a second
copy.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def program_seed(seed: int) -> int:
    """The 31-bit seed handed to the program and to ``PRNGKey``: JAX
    keeps only the low 32 bits of a wider seed."""
    return int(seed) % 2147483647


def _probe_prompt(prompt_len: int):
    """``0+0=?`` filled with ``#`` to the traffic's prompt length, in the
    program's character ids (digits from 3, ``+`` 13, ``=`` 17, ``?`` 18,
    ``#`` 19)."""
    ids = [3, 13, 3, 17, 18] + [19] * (prompt_len - 5)
    return jnp.asarray(ids[:prompt_len], jnp.int32)


def _probe(ref, spec, params, key, prompt_len):
    """Sample ``probe_new`` tokens after a probe prompt with the shaped
    head (digits not yet placed), and return the mean direction ``u`` of
    the final hidden state at the sampling positions, the mean cosine
    ``c`` of those states with ``u``, and the mean log-sum-exp ``L`` of
    the logits there."""
    w = spec["weights"]
    n_new = int(w["probe_new"])
    head = shape_head(spec, params["lm_head"], None)
    total = prompt_len + n_new
    toks = jnp.zeros((total,), jnp.int32).at[:prompt_len].set(
        _probe_prompt(prompt_len))

    def body(i, carry):
        toks, k = carry
        h = ref.hidden(params, spec, toks[None])[0, i - 1]
        k, sk = jax.random.split(k)
        t = jax.random.categorical(sk, h @ head)
        return toks.at[i].set(t.astype(jnp.int32)), k

    toks, _ = jax.lax.fori_loop(prompt_len, total, body,
                                (toks, jax.random.fold_in(key, 0x5EED)))
    h = ref.hidden(params, spec, toks[None])[0, prompt_len - 1:total - 1]
    hn = h / jnp.linalg.norm(h, axis=-1, keepdims=True)
    u = jnp.mean(hn, axis=0)
    u = u / jnp.linalg.norm(u)
    lse = jax.nn.logsumexp(h @ head, axis=-1)
    return u, jnp.mean(hn @ u), jnp.mean(lse)


def shape_head(spec, head, probe):
    """Scale the head, zero the pad and end columns, and (given the
    probe) place the digits' shared column."""
    w = spec["weights"]
    head = head * w["head_scale"]
    if probe is not None:
        u, c, lse = probe
        q = w["answer_share"]
        logit = lse + np.log(q / (2.0 * (1.0 - q)))
        col = (logit / (np.sqrt(spec["d_model"]) * c)) * u
        ids = jnp.asarray(w["answer_ids"])
        head = head.at[:, ids].set(
            jnp.broadcast_to(col[:, None], (head.shape[0], len(ids))))
    return head.at[:, jnp.asarray(w["zeroed_ids"])].set(0.0)


def make(ref, spec, seed: int, prompt_len: int, shardings=None):
    """(params, probe): the weights, on the device, in one jitted call."""
    def build(key):
        p = ref.init(spec, key)
        probe = _probe(ref, spec, p, key, prompt_len)
        p["lm_head"] = shape_head(spec, p["lm_head"], probe)
        return p, probe

    key = jax.random.PRNGKey(program_seed(seed))
    if shardings is None:
        return jax.jit(build)(key)
    return jax.jit(build, out_shardings=(shardings, None))(key)


def regenerate(ref, spec, key, probe):
    """The weights again from the key and the probe direction, without a
    forward pass: cheap enough to fuse into a reduction."""
    p = ref.init(spec, key)
    p["lm_head"] = shape_head(spec, p["lm_head"], probe)
    return p


def leaf_names(tree):
    return [jax.tree_util.keystr(k) for k, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


def _norms(leaves):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in leaves])


@jax.jit
def leaf_norms(tree):
    return _norms(jax.tree.leaves(tree))


def change_norms(ref, spec, seed: int, params, probe):
    """Per-leaf norm of ``params`` minus the seed's weights, with the
    seed's weights regenerated inside the reduction."""
    key = jax.random.PRNGKey(program_seed(seed))

    @jax.jit
    def f(params, key, probe):
        w0 = regenerate(ref, spec, key, probe)
        return _norms([a - b for a, b in zip(jax.tree.leaves(params),
                                             jax.tree.leaves(w0))])
    return np.asarray(f(params, key, probe), np.float64)
