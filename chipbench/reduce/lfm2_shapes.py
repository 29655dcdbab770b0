"""Shapes of a hybrid of short-convolution and attention layers (LFM2), counted
from the leaf shapes of the cache the engine holds and the published sizes:
which leaf is the per-sequence state, what a sequence holds in it, and which
results of a device trace are certainly the conv operator's.

The engine's cache tree has the paged pool's leaves ``[L_attn, NB, KV, bs,
width]`` (rank 5) and ONE state leaf ``[L_conv, slots + 1, k, D]`` (rank 4: ``k
= conv_L_cache - 1`` remembered values of width ``D`` a conv layer a slot; the
last slot is the trash slot).  ``shapes.py`` counts a dense decoder and knows
neither."""

STATE_VALUE_BYTES = 2  # every serving configuration's cache is bfloat16; the entry hands no dtype


def state_leaf(pool_shapes):
    """The state's shape ``(L_conv, slots + 1, k, D)``, or None where the
    engine holds no such leaf (every configuration before this one)."""
    found = [tuple(s) for s in pool_shapes or () if len(s) == 4]
    return found[0] if len(found) == 1 else None


def state_bytes_per_seq(pool_shapes, value_bytes: int = STATE_VALUE_BYTES):
    """What one live sequence holds outside the paged pool, whatever its
    length: ``L_conv x k x D`` values."""
    leaf = state_leaf(pool_shapes)
    if leaf is None:
        return None
    layers, _, kept, width = leaf
    return layers * kept * width * value_bytes


def is_whole_state(dims, leaf) -> bool:
    """A result that is the whole state: as held, or with its layers and slots
    on one axis, as the layer scan carries it."""
    layers, slots, kept, width = leaf
    return tuple(dims) in (tuple(leaf), (layers * slots, kept, width))


def is_state_move(dims, leaf) -> bool:
    """A result that is the whole state or a batch's rows of it: ``[n, k, D]``
    read from the slots, ``[n, k + 1, D]`` the rows with a decode step's one
    new value beside them, for ``n`` up to the slots."""
    _, slots, kept, width = leaf
    dims = tuple(dims)
    return is_whole_state(dims, leaf) or (
        len(dims) == 3 and dims[2] == width and dims[1] in (kept, kept + 1) and 0 < dims[0] < slots)


def is_mixer_result(dims, leaf, hidden: int) -> bool:
    """A result that is certainly the conv operator's: the input projection's
    ``[.., 3 x hidden]`` (B, C and X side by side: no other layer of the model
    is that wide) or a move of the state.  NOT among them, because nothing
    tells them from every other per-token operation of the step: the gate,
    the taps' products and the output projection, all ``[slots, hidden]``."""
    dims = tuple(dims)
    return bool(dims) and (dims[-1] == 3 * hidden or is_state_move(dims, leaf))
