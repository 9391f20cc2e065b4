"""Room on the interpreter's stack for a call that goes deep and loops there.

CPython (3.11 on) keeps a thread's frames in chunks of 16 KiB and gives a
chunk back to the system as soon as the frame at its base returns.  A loop
whose calls cross the edge of the current chunk therefore maps and unmaps
a chunk on EVERY call, and JAX's tracing of a statically unrolled kernel is
such a loop, ten million calls long: where the edge falls depends on the
size of every frame beneath, so one more frame anywhere under the call
(a wrapper, a dispatch method) can double the time a program takes to
trace, on one machine and not on another (PERF.md section 6, PR 41: a
serving cell's warm set-up 66 -> 100 s, all of it page faults).

A frame too large for a chunk gets one of its own, the next power of two
in size, and that chunk lives as long as the frame does.
:func:`stack_room` declares such a frame for the function it decorates:
every call made from inside it, however deep, stays within that chunk and
meets no edge.  The words are address space only: no value is ever pushed
there, so no page of it is touched.
"""

import functools
import types

# the value stack a decorated function declares, in words of 8 bytes: its
# frame takes half of a chunk of 2**16 words (512 KiB mapped while it
# runs), and 2**15 words, a thousand frames, are left for the calls above
WORDS = (1 << 15) + 64


def stack_room(fn):
    """``fn`` with a frame that carries room for every call it makes."""
    code = fn.__code__
    roomy = types.FunctionType(
        code.replace(co_stacksize=max(code.co_stacksize, WORDS)),
        fn.__globals__, fn.__name__, fn.__defaults__, fn.__closure__,
    )
    roomy.__kwdefaults__ = fn.__kwdefaults__
    return functools.update_wrapper(roomy, fn)
