"""The state-space (Mamba-2, SSD) recurrence for serving.

A Mamba-2 head ``n`` carries a state ``S[n]`` of ``P x N`` values (its
``P`` channels by ``d_state``) from token to token:

    S_t[n] = exp(dt_t[n] A[n]) S_{t-1}[n] + dt_t[n] (x_t[n] outer B_t)
    y_t[n] = S_t[n] C_t

(``D[n] x_t[n]``, the skip, is the caller's). Two forms compute it, one a
program calls for each shape it has:

* :func:`chunk_scan` (``scan.py``): a prefill chunk of ``T`` tokens at
  once in its dual form, from the slot's state before the chunk to its
  state after it; jnp, under ``jax.named_scope("ssm_chunk")``.
* :func:`decode_update` (``decode.py``): one token a slot, every slot of
  a layer, the state updated where it lies in the donated pool: the
  Pallas kernel ``ssm_decode`` where :func:`decode_kernel_runs`, else the
  same arithmetic in jnp.

A pool holds a slot's state PACKED: the ``H x P`` channels of a layer as
rows of 128 lanes, ``d_state`` down the sublanes (``[R, N, 128]``,
:func:`from_heads` / :func:`to_heads`), so that every tile is whole and
the decode update is whole-vreg arithmetic with a sublane reduction.
"""

from deepspeed_tpu.ops.ssm.decode import (decode_kernel_runs,  # noqa: F401
                                          decode_update)
from deepspeed_tpu.ops.ssm.scan import (chunk_scan, from_heads,  # noqa: F401
                                        packed_rows, to_heads)
