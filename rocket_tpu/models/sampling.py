"""Token-sampling core shared by ``generate()`` and ``rocket_tpu.serve``.

One implementation of temperature / top-k / top-p sampling and the
EOS-freeze step, accepting either Python scalars (the ``generate()`` path
— compiled per knob combination, op-for-op identical to the historical
``_sample_token``) or per-row arrays (the serving path, where every slot
in a fixed-shape decode wave carries its own sampling parameters and the
knobs must be runtime values so admission never retraces).

Conventions for the per-row (array) forms:

* ``temperature <= 0`` — greedy argmax for that row;
* ``top_k <= 0`` — no top-k filter for that row;
* ``top_p >= 1`` — no nucleus filter for that row;
* ``eos < 0`` — EOS freezing disabled for that row (frozen rows fill
  with 0 when they hit a length limit instead).

What a per-row call costs follows what its rows ask for, decided on the
device from the knob arrays (:func:`sample_branch`; the compiled fn is
one, whatever the knobs):

* no row samples — one argmax over (rows, V);
* some row samples, none of those filters — per-row keys and one
  categorical draw more;
* some sampling row filters — ONE descending sort of (rows, V) for both
  filters, a softmax and a cumsum over it, and the draw. On a TPU v5e the
  sort is by far the dearest operation of a decode wave (PERF.md §5), so
  a wave of greedy rows must not run it.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["sample_tokens", "sample_branch", "SAMPLE_BRANCHES",
           "freeze_after_eos"]


def _scalar(value) -> bool:
    """Python OR numpy scalar (ndim-0) — routed to the static path; jax
    arrays (even 0-d) and per-row numpy arrays take the runtime path."""
    return isinstance(value, (int, float, np.integer, np.floating))


#: What a call of the per-row path does, by :func:`sample_branch`'s index.
SAMPLE_BRANCHES = ("argmax", "sample", "filter")


def sample_branch(temperature, top_k, top_p, run=None):
    """Index into :data:`SAMPLE_BRANCHES`: the work the rows of ONE call
    ask for, from its per-row knob arrays (numpy on the host, jax on the
    device: the serve scheduler's counter and the device's ``switch`` call
    this one function). ``run`` masks the rows that count, where given.

    * 0 ``argmax``: no row that counts samples (``temperature <= 0``);
    * 1 ``sample``: some row samples, none of the sampling rows filters
      (``top_k <= 0`` and ``top_p >= 1`` in each of them);
    * 2 ``filter``: some sampling row filters.
    """
    samples = temperature > 0
    if run is not None:
        samples = samples & run
    filters = samples & ((top_k > 0) | (top_p < 1))
    return samples.any().astype(np.int32) + filters.any().astype(np.int32)


def _draw(key, salt, scaled):
    """One token a row from ``scaled`` logits: per-row subkeys for an
    array ``salt``, one subkey shared by the batch for a scalar."""
    if getattr(salt, "ndim", 0) > 0:
        keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
            key, jnp.asarray(salt)
        )
        return jax.vmap(
            lambda k_row, l_row: jax.random.categorical(k_row, l_row)
        )(keys, scaled)
    return jax.random.categorical(
        jax.random.fold_in(key, salt), scaled, axis=-1
    )


def sample_tokens(logits, key, salt, temperature, top_k=None, top_p=None,
                  run=None):
    """Sample next tokens from ``logits`` (..., V).

    ``temperature``/``top_k``/``top_p`` may each be a Python scalar
    (static — baked into the compiled fn, exactly the historical
    ``generate()`` behavior) or a per-row array over the leading dims
    (runtime — one compiled fn serves every knob combination; with any
    of the three an array, the scalars among them are broadcast to rows).
    ``salt`` is folded into ``key``: a scalar derives ONE subkey shared
    across the batch (the ``generate()`` convention, so both its paths
    sample identically for the same key), an array derives per-row subkeys
    (the serve convention: each slot streams independent of its neighbors).

    ``run`` (runtime path only; bool per row) names the rows whose token
    is read: a row outside it asks for no sampling and gets its argmax.
    """
    logits = logits.astype(jnp.float32)
    if all(v is None or _scalar(v) for v in (temperature, top_k, top_p)):
        return _sample_static(logits, key, salt, temperature, top_k, top_p)
    return _sample_rows(logits, key, salt, temperature, top_k, top_p, run)


def _sample_static(logits, key, salt, temperature, top_k, top_p):
    """Scalar knobs: one compiled fn per knob combination."""
    if top_k is not None:
        kth = jax.lax.top_k(logits, int(top_k))[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if temperature <= 0:
        return jnp.argmax(logits, axis=-1)  # filters don't move the argmax
    scaled = logits / temperature
    if top_p is not None and top_p < 1.0:
        # Nucleus: keep the smallest descending-prob prefix whose mass
        # reaches top_p (the first token always survives: cum - p < top_p).
        ranked = jnp.sort(scaled, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(ranked, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = cum - probs < float(top_p)
        cutoff = jnp.min(
            jnp.where(keep, ranked, jnp.inf), axis=-1, keepdims=True
        )
        scaled = jnp.where(scaled < cutoff, -jnp.inf, scaled)
    return _draw(key, salt, scaled)


def _sample_rows(logits, key, salt, temperature, top_k, top_p, run):
    """Per-row knobs: the call does the work its rows ask for, chosen on
    the device by :func:`sample_branch` (no host sync, no retrace).

    * ``argmax``: the argmax. No sort, no softmax, no keys, no draw.
    * ``sample``: per-row keys and one categorical draw on the logits
      over the temperature. No sort.
    * ``filter``: ONE descending sort serves both filters. The k-th value
      is read from it; the nucleus's ranking is the same sorted array with
      the top-k mask applied and divided by the row's temperature (masking
      below the k-th value and dividing by a positive number are monotone,
      so ``sort(mask(x) / t)`` is ``mask(sort(x)) / t`` element for
      element). Then softmax, cumsum, the cutoff and the draw.

    Every branch returns what ``filter`` would: the others leave out
    work whose result no row reads.
    """
    rows = logits.shape[:-1]
    vocab = logits.shape[-1]
    t = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), rows)
    k = jnp.broadcast_to(
        jnp.asarray(0 if top_k is None else top_k, jnp.int32), rows
    )
    p = jnp.broadcast_to(
        jnp.asarray(1.0 if top_p is None else top_p, jnp.float32), rows
    )
    if run is not None:
        t = jnp.where(run, t, 0.0)
    greedy = jnp.argmax(logits, axis=-1)  # filters don't move the argmax
    warm = jnp.where(t > 0, t, 1.0)[..., None]

    def unfiltered():
        return _draw(key, salt, logits / warm)

    def filtered():
        ranked = jnp.sort(logits, axis=-1)[..., ::-1]
        kth = jnp.take_along_axis(
            ranked, (jnp.clip(k, 1, vocab) - 1)[..., None], axis=-1
        )
        on = k[..., None] > 0
        scaled = jnp.where(on & (logits < kth), -jnp.inf, logits) / warm
        ranked = jnp.where(on & (ranked < kth), -jnp.inf, ranked) / warm
        # Nucleus: keep the smallest descending-prob prefix whose mass
        # reaches top_p (the first token always survives: cum - p < top_p).
        probs = jax.nn.softmax(ranked, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = cum - probs < p[..., None]
        cutoff = jnp.min(
            jnp.where(keep, ranked, jnp.inf), axis=-1, keepdims=True
        )
        cutoff = jnp.where(p[..., None] < 1.0, cutoff, -jnp.inf)  # opt-out
        return _draw(key, salt, jnp.where(scaled < cutoff, -jnp.inf, scaled))

    sampled = jax.lax.switch(
        sample_branch(t, k, p), [lambda: greedy, unfiltered, filtered]
    )
    return jnp.where(t > 0, sampled, greedy)


def freeze_after_eos(nxt, done, eos):
    """Force the fill token for rows whose carried ``done`` flag is set
    (they GENERATED an EOS or hit their length limit on an earlier step —
    prompt EOS never sets the flag), and fold this step's token into the
    flag. ``eos`` is a Python int (always enabled — the legacy scalar
    path) or a per-row int array where ``< 0`` disables EOS for that row
    (such rows fill with 0 once frozen). O(B) per step."""
    if isinstance(eos, int):
        nxt = jnp.where(done, eos, nxt)
        return nxt, done | (nxt == eos)
    eos = jnp.asarray(eos, nxt.dtype)
    enabled = eos >= 0
    nxt = jnp.where(done, jnp.where(enabled, eos, 0), nxt)
    return nxt, done | (enabled & (nxt == eos))
