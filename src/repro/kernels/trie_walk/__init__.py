"""Fused trie-walk megakernel: the whole subtree walk in one dispatch.

* ``ref.py``       - ``trie_walk_core``: the jnp slot-topological walk
                     over in-kernel frontier buffers (the kernel's
                     oracle), bit-identical to the per-level scan in
                     repro.serving.batch.
* ``trie_walk.py`` - ``trie_walk_blocked``: the Pallas kernel gridded
                     over (sequence, depth-1 subtree) cells, its body
                     the same walk in a form Mosaic lowers, behind the
                     same interpret/lane-pad backend auto-select as the
                     containment kernel.

Serving entry point: ``repro.serving.batch.fused_trie_walk`` (gathers
per-cell arrays inside one jitted program); layout registration:
``bank_layout="trie_fused"`` (repro.serving.layouts / server).
"""
from .ref import trie_walk_core  # noqa: F401
from .trie_walk import trie_walk_blocked  # noqa: F401
