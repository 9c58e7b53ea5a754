"""The selective scan of a state-space (Mamba-1) mixer, behind the helper
seam ``get_helper("selective_scan")``.

The recurrence, per batch row, channel ``d`` and state column ``n``, all in
float32::

    h_t[n, d] = exp(dt_t[d] * A[n, d]) * h_{t-1}[n, d]
                + (dt_t[d] * x_t[d]) * B_t[n]
    y_t[d]    = sum_n h_t[n, d] * C_t[n]

THE STATE LIES ``[N, D]``: the channels (``D`` = 5,120 at Jamba's widths,
40 whole lanes of 128) on the minor axis and the ``N`` = 16 state columns on
the sublanes, so a slot's state is 80 whole vector registers; ``[D, N]``
would pad 16 columns out to 128 lanes, eight times the memory and the
traffic.  ``A`` is handed in the same layout.

One form a backend.  ``chunked_scan`` is the ``lax`` form every backend
runs: a ``lax.scan`` over chunks of ``SCAN_CHUNK`` time steps; a chunk's
decays ``exp(dt A)`` and inputs ``dt x B`` are made for all its steps at
once (``[chunk, N, D]``, the vector unit's work, 5.2 MB each at a chunk of
16 and Jamba's widths), the recurrence then walks the chunk's steps in
order, each step's output its state contracted with ``C_t``.  Nothing
of size ``[T, D, N]`` exists for a whole bucket (336 MB a layer at 1,024
tokens).  No Pallas kernel ships: the measurements that decided it are in
``PERF.md`` (PR 39).

``live`` [B] (a prefill bucket's real tokens): positions at or past it
leave the state untouched — a decay of 1 and an input of 0 — so the state
returned is the one after the last REAL token; their outputs are finite and
mean nothing.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

# time steps a trip of the chunk loop; chosen on the chip (PERF.md, PR 39)
SCAN_CHUNK = 16


def stepwise_scan(x, dt, a, b, c, h0, live=None):
    """The recurrence one time step a trip: the plain form, and the layers'
    built-in path where helpers are off.  ``x``, ``dt`` [B, T, D]; ``a``
    [N, D]; ``b``, ``c`` [B, T, N]; ``h0`` [B, N, D]; ``live`` [B] or None.
    Returns ``(y [B, T, D], h_T [B, N, D])``."""
    return chunked_scan(x, dt, a, b, c, h0, live, chunk=1)


def chunked_scan(x, dt, a, b, c, h0, live=None, chunk=None):
    """As ``stepwise_scan``, ``chunk`` (``SCAN_CHUNK``) time steps a trip of
    the loop (the last chunk of a length that ``chunk`` does not divide is
    padded with steps that keep the state)."""
    bsz, t, d = x.shape
    chunk = max(1, min(int(chunk or SCAN_CHUNK), t))
    trips = -(-t // chunk)
    pad = trips * chunk - t
    if live is None and pad:
        live = jnp.full((bsz,), t, jnp.int32)

    def chunks(v):          # [B, T, W] -> [trips, B, chunk, W]
        if pad:
            v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
        return jnp.moveaxis(v.reshape(bsz, trips, chunk, v.shape[-1]), 1, 0)

    steps = jnp.arange(trips * chunk, dtype=jnp.int32).reshape(trips, chunk)

    def trip(h, inp):
        xc, dtc, bc, cc, at = inp           # [B, chunk, D | N], [chunk]
        decay = jnp.exp(dtc[:, :, None, :] * a)          # [B, chunk, N, D]
        drive = (dtc * xc)[:, :, None, :] * bc[..., None]
        if live is not None:
            # a step at or past ``live`` keeps the state
            valid = (at[None, :] < live[:, None])[:, :, None, None]
            decay = jnp.where(valid, decay, 1.0)
            drive = jnp.where(valid, drive, 0.0)
        ys = []
        for i in range(chunk):
            h = decay[:, i] * h + drive[:, i]
            ys.append(jnp.sum(h * cc[:, i, :, None], axis=1))
        return h, jnp.stack(ys, axis=1)

    h, ys = lax.scan(trip, h0, (chunks(x), chunks(dt), chunks(b), chunks(c),
                                steps))
    y = jnp.moveaxis(ys, 0, 1).reshape(bsz, trips * chunk, d)
    return (y[:, :t] if pad else y), h


def single_step(x, dt, a, b, c, h):
    """One token a row, no loop: ``x``, ``dt`` [B, D]; ``b``, ``c`` [B, N];
    ``h`` [B, N, D].  Returns ``(y [B, D], h')``: one pass over the rows'
    states."""
    h = jnp.exp(dt[:, None, :] * a) * h + (dt * x)[:, None, :] * b[..., None]
    return jnp.sum(h * c[..., None], axis=1), h


class SelectiveScanHelper:
    """The seam's object: ``scan`` for a chunk or a sequence, the ``lax``
    form on every backend."""

    def scan(self, x, dt, a, b, c, h0, live=None):
        return chunked_scan(x, dt, a, b, c, h0, live)

    def describe(self, t: int, path: str = "") -> str:
        """How a program of ``t`` positions a row is chunked, for the
        warm-up's log (one form for every ``path``)."""
        chunk = max(1, min(SCAN_CHUNK, t))
        return (f"lax chunked scan, {-(-t // chunk)} trips of {chunk} time "
                f"steps")
