"""One host buffer a serving call.

A serving program's small per-call operands — token ids, lengths, block
tables, the sampling vectors: a few hundred bytes to ~100 KB together — used
to reach the device as eight to ten ``jnp.asarray`` puts, each of which
returns on a TPU only when its transfer has completed (~0.25 ms, the thread
asleep, the device idle: 2.7-5.5 ms of every serving step, PERF.md section
5).  An :class:`OperandLayout` fixes, when a program is built, where each of
them lies in ONE contiguous 32-bit buffer: the host writes the fields
(:meth:`OperandLayout.fill`), a snapshot of the buffer rides into the jitted
call as one numpy operand — the transfer starts inside the call, nothing
blocks before it — and the program takes it apart at its head
(:meth:`OperandLayout.unpack`: static slices, reshapes and bitcasts, no
arithmetic, so every field arrives with the bits it was
written with).
"""

from typing import Any, Mapping, NamedTuple, Tuple

import jax
import numpy as np


class Field(NamedTuple):
    """One operand's place in the buffer (``offset`` and ``words`` in
    32-bit words)."""
    name: str
    shape: Tuple[int, ...]
    dtype: np.dtype
    offset: int

    @property
    def words(self) -> int:
        return int(np.prod(self.shape, dtype=np.int64))


class OperandLayout:
    """The layout of one program's packed operands.

    ``operands`` maps each operand's name to its ``jax.ShapeDtypeStruct`` (or
    a dict of them: block tables by layer kind), in the positional order the
    program's body takes them.  A field is 32 bits wide — int32, uint32 or
    float32 — or bool, which travels as a 0 / 1 word."""

    def __init__(self, operands: Mapping[str, Any]):
        self.names = tuple(operands)
        paths, self._tree = jax.tree_util.tree_flatten_with_path(
            tuple(operands.values()))
        fields, offset = [], 0
        for path, leaf in paths:
            dtype = np.dtype(leaf.dtype)
            if dtype != np.bool_ and dtype.itemsize != 4:
                raise TypeError(
                    f"operand {path}: {dtype} is not a 32-bit type or bool")
            name = self.names[path[0].idx] + "".join(
                f".{p.key}" for p in path[1:])
            fields.append(Field(name, tuple(leaf.shape), dtype, offset))
            offset += fields[-1].words
        self.fields = tuple(fields)
        self.words = offset
        self.buffer = np.zeros(offset, np.int32)
        # a bool field is written through an int32 view: numpy casts it to 0 / 1
        self._views = tuple(
            self.buffer[f.offset:f.offset + f.words]
            .view(np.int32 if f.dtype == np.bool_ else f.dtype)
            .reshape(f.shape) for f in fields)

    @property
    def nbytes(self) -> int:
        return self.buffer.nbytes

    def fill(self, *operands) -> np.ndarray:
        """Write one call's operands (same order and structure as the
        layout's) and return the array to hand to the call: a private
        snapshot of the buffer, which nobody writes again, so the buffer
        itself may be refilled at once.  Neither runtime is done with a
        numpy operand when the call returns: XLA:CPU aliases an aligned one
        for as long as the asynchronous execution lasts, and on a TPU v5e a
        buffer scribbled over right after the enqueue changed 1-4 results
        in 400 (PERF.md section 6, PR 38).  The copy is 2-10 us."""
        leaves = jax.tree_util.tree_leaves(operands)
        if len(leaves) != len(self._views):
            raise ValueError(f"{len(leaves)} operands for a layout of "
                             f"{len(self._views)} fields {self.names}")
        for view, leaf in zip(self._views, leaves):
            if np.shape(leaf) != view.shape:
                raise ValueError(f"operand of shape {np.shape(leaf)} for a "
                                 f"field of shape {view.shape}")
            view[...] = leaf
        return self.buffer.copy()

    def unpack(self, buffer):
        """Traced, at the head of the program: the operands back out of
        ``buffer``, as the tuple the body takes."""
        leaves = []
        for f in self.fields:
            x = jax.lax.slice(buffer, (f.offset,), (f.offset + f.words,)) \
                .reshape(f.shape)
            if f.dtype == np.bool_:
                x = x != 0
            elif f.dtype != np.int32:
                x = jax.lax.bitcast_convert_type(x, f.dtype)
            leaves.append(x)
        return jax.tree_util.tree_unflatten(self._tree, leaves)
