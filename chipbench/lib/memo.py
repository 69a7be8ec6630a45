"""Empty the program's memos between warm-up and the window."""
from __future__ import annotations

import functools
import sys


def clear_program_memos() -> int:
    """Clear every ``functools`` cache held at the top level of a loaded
    ``repro`` module, so that the window starts with the memos of a
    fresh process while its compiled programs stay warm.  Returns how
    many caches were cleared."""
    n = 0
    for name, mod in list(sys.modules.items()):
        if not name.startswith("repro") or mod is None:
            continue
        for obj in list(vars(mod).values()):
            if isinstance(obj, functools._lru_cache_wrapper):
                obj.cache_clear()
                n += 1
    return n
