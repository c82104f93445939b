"""The classic step's batch as ONE int32 buffer: one host-to-device copy.

The step program takes 14 small arrays (12 int32, 2 float32).  Handed to
``jax.device_put`` as a dict they cost 14 copies of about 0.24 ms each on
the chip's host, whatever their size.  ``BatchLayout`` lays them out back to
back in one int32 buffer at offsets that are a pure function of the bucket
``(T, S, Q, B)``: the engine fills named numpy views of the buffer (no
second host copy), copies it once, and the step program takes it apart
again with static slices and reshapes, which XLA folds away.  The two float
arrays travel by bit pattern (``ndarray.view`` on the host,
``lax.bitcast_convert_type`` in the program), so they arrive bit-exact.

The buffer's length does not determine the bucket (T + ... can collide), so
the layout itself reaches the program as a static, hashable argument.

A block-diffusion model's step (``R`` > 1: the slots of a block) samples at
every slot of every row's block: ``sample_idx`` grows to ``S * R`` entries
and two arrays join the buffer, which slots are masked and how many the pass
reveals a row.  ``R`` = 1 is the autoregressive layout, field for field.
A stack with recurrent state beside its pages (``state``) adds three arrays
a row; without it the layout is the one above, field for field.  A cache in
groups by layer kind (``groups``, kv_cache.py) adds the window group's block
table and write slots beside the full group's, and the two groups' page
counts (where each layer's region of the one buffer starts follows from
them): still the one copy.

Tokens fed on the device (``feed_tokens``): a ``token_ids`` entry
``-(row + 1)`` names the token the PREVIOUS step sampled for its row
``row``, which the host does not hold yet when it fills this step's buffer
(the engine composes a step while its predecessor runs, engine.py).  The
autoregressive step program takes the previous step's sampled ids as one
more operand of a fixed shape, replaces such entries before the embedding
and returns its own ids in that shape: the buffer stays the one copy.

Stacked (SPMD dp) mode: ``dp > 1`` gives a ``[dp, size]`` buffer, one row a
shard, sharded over the leading axis; every array comes out ``[dp, ...]``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_I32, _F32 = np.dtype(np.int32), np.dtype(np.float32)


def _fields(T: int, S: int, Q: int, B: int, R: int = 1,
            state: bool = False, groups: bool = False):
    """(name, shape, dtype, value of a padded slot) of the step's batch."""
    fields = (
        ("token_ids", (T,), _I32, 0),
        ("positions", (T,), _I32, 0),
        ("token_seq_ids", (T,), _I32, 0),
        ("token_qpos", (T,), _I32, 0),
        ("slot_mapping", (T,), _I32, 0),      # local block 0 = trash
        ("block_tables", (S, B), _I32, 0),
        ("seq_lens", (S,), _I32, 0),
        ("sample_idx", (S * R,), _I32, 0),
        ("qtok_idx", (S, Q), _I32, T),        # T = padded-q sentinel
        ("temperature", (S,), _F32, 0.0),
        ("top_k", (S,), _I32, 0),
        ("top_p", (S,), _F32, 1.0),
        ("seeds", (S,), _I32, -1),
        ("gen_idx", (S,), _I32, 0),
    )
    if R > 1:
        fields += (
            ("slot_masked", (S, R), _I32, 0),     # 1 = the slot holds the mask
            ("reveal_quota", (S,), _I32, 0),      # slots the pass reveals
        )
    if state:
        fields += (
            ("state_slot", (S,), _I32, 0),        # 0 = the pool's trash slot
            ("query_start", (S,), _I32, 0),       # the row's first token
            ("query_len", (S,), _I32, 0),         # tokens of its chunk
        )
    if groups:
        fields += (
            ("block_tables_w", (S, B), _I32, 0),  # 0 = trash: passed, unheld
            ("slot_mapping_w", (T,), _I32, 0),
            ("kv_group_blocks", (2,), _I32, 0),   # pages: full, window group
        )
    return fields


@functools.lru_cache(maxsize=None)
def _slots(T: int, S: int, Q: int, B: int, R: int = 1,
           state: bool = False, groups: bool = False):
    """((name, start, stop, shape, dtype), ...), the buffer's length, and
    ((start, stop, int32 bit pattern), ...) of the defaults that are not 0."""
    slots, fills, at = [], [], 0
    for name, shape, dtype, pad in _fields(T, S, Q, B, R, state, groups):
        stop = at + math.prod(shape)
        slots.append((name, at, stop, shape, dtype))
        bits = int(np.array(pad, dtype).view(np.int32))
        if bits:
            fills.append((at, stop, bits))
        at = stop
    return tuple(slots), at, tuple(fills)


@dataclasses.dataclass(frozen=True)
class BatchLayout:
    """Bucket of one step program: ``T`` token rows, ``S`` sequence rows,
    ``Q`` query slots a sequence, ``B`` block-table columns, per shard;
    ``R`` sampled slots a sequence (a block-diffusion model's block);
    ``state``: the rows also name their slot of the recurrent-state pool
    and their chunk's place in the batch (a stack with a state-space
    mixer, ops/ssm.py); ``groups``: the pages go by layer kind, and the
    batch brings the window group's table and write slots too."""
    T: int
    S: int
    Q: int
    B: int
    dp: int = 1
    R: int = 1
    state: bool = False
    groups: bool = False

    def _slots(self):
        return _slots(self.T, self.S, self.Q, self.B, self.R, self.state,
                      self.groups)

    @property
    def shape(self) -> Tuple[int, ...]:
        size = self._slots()[1]
        return (size,) if self.dp == 1 else (self.dp, size)

    def new_buffer(self) -> np.ndarray:
        """A fresh host buffer holding an empty batch (every slot padded).
        Fresh each step: the copy of the last one may still be in flight."""
        buf = np.zeros(self.shape, np.int32)
        for start, stop, bits in self._slots()[2]:
            buf[..., start:stop] = bits
        return buf

    def views(self, row: np.ndarray) -> Dict[str, np.ndarray]:
        """The batch's arrays as views of one shard's 1-D ``row`` of the
        buffer: what ``_fill_batch`` writes lands in the buffer."""
        return {name: row[start:stop].view(dtype).reshape(shape)
                for name, start, stop, shape, dtype in self._slots()[0]}

    def unpack(self, packed: jax.Array) -> Dict[str, jax.Array]:
        """Inside the step program: the dict ``model.forward`` and
        ``sampling_ops.sample`` take, from the one buffer."""
        if packed.shape != self.shape or packed.dtype != jnp.int32:
            raise ValueError(f"packed batch {packed.dtype}{packed.shape} "
                             f"is not {self}'s int32{self.shape}")
        out = {}
        for name, start, stop, shape, dtype in self._slots()[0]:
            x = jax.lax.slice_in_dim(packed, start, stop, axis=-1)
            x = x.reshape(packed.shape[:-1] + shape)
            out[name] = (x if dtype == _I32 else
                         jax.lax.bitcast_convert_type(x, jnp.float32))
        return out


def feed_tokens(batch: Dict[str, jax.Array],
                prev_ids: jax.Array) -> Dict[str, jax.Array]:
    """Inside the step program: ``batch`` with every ``token_ids`` entry
    ``-(row + 1)`` replaced by ``prev_ids[row]``, the id the previous step
    sampled for that row.  A batch that names no row comes back as it is
    (token ids are never negative)."""
    tok = batch["token_ids"]
    # A select over the few rows, not a gather: [T, max_num_seqs] compares
    # that fuse into one reduction.
    named = tok[:, None] == -1 - jnp.arange(prev_ids.shape[0], dtype=tok.dtype)
    fed = jnp.sum(jnp.where(named, prev_ids, 0), axis=-1)
    return dict(batch, token_ids=jnp.where(tok < 0, fed, tok))
