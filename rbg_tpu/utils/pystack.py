"""Room for a thread's Python frames, so that tracing does not map memory.

CPython (3.11 on) keeps a thread's frames in chunks of 16 KiB and gives a
chunk back to the system the moment the first frame in it returns. Code
that calls and returns across a chunk's edge over and over maps and unmaps
16 KiB every time, and that is what JAX does when it traces a step program
and lowers a Pallas kernel's body: a few hundred frames deep, a rule an
equation. Which loop straddles an edge follows from how many frames lie
above it, so a warm start moves by tens of seconds with an edit that adds
a frame and leaves every program as it was: lowering the 36 packed programs
of one benchmark cell took 83 s from a bare thread and 120 s from six
frames deeper, on a host whose sandboxed kernel makes ``mmap`` dear
(PERF.md, PR 42; ROADMAP S8 (b)).

``on_roomy_stack(fn, ...)`` calls ``fn`` from a frame that claims a little
over 1 MiB of operand stack, of which it touches nothing. The interpreter
sizes a frame's chunk to the next power of two, 2 MiB here, so the half
behind the frame holds every frame below it, some thousands deep, and
nothing is mapped or unmapped until the call returns. The pages cost
memory only where frames reach them.
"""

from __future__ import annotations

import types

# Slots (8 bytes each) of the frame's operand stack: a little over a
# power of two, so that the chunk is twice the frame.
_SLOTS = (1 << 17) + 64


def _call(fn, args, kwargs):
    return fn(*args, **kwargs)


_roomy = types.FunctionType(_call.__code__.replace(co_stacksize=_SLOTS),
                            globals(), "_call")


def on_roomy_stack(fn, *args, **kwargs):
    """``fn(*args, **kwargs)``, every frame under it in one chunk."""
    return _roomy(fn, args, kwargs)
