"""A step's ONE array for the host (ISSUE 35), and nothing else.

For every device step the serving loop makes one device→host read:
the int32 vector the step lays out here as ``[tokens | flags | stats
tail]``.  Since ISSUE 37 a decode step's vector is read one step late —
the next step is launched first, its input tokens fed back on the device
(``cache.last_tokens``) — and the layout is what it was.  It is known in THIS module and nowhere else —
:func:`host_vector` packs it inside the compiled step (or on the host,
for the protocol audit's stub engine), :func:`peel_step` is the inverse,
and whoever holds such a vector (the scheduler, a drafter, a benchmark
loop that feeds the tokens back, a test) peels it with that."""
import jax.numpy as jnp

__all__ = ["host_vector", "peel_step"]


def host_vector(tokens, *flags, tail=None, xp=jnp):
    """Everything the host reads of one step, as ONE int32 vector laid
    out ``[tokens | flags | stats tail]`` (ISSUE 35): the sampled tokens
    flattened, then each ``[slots]`` flag vector in the order given
    (decode: ``truncated``; verify: ``n_emit``, ``truncated``), then the
    counters of a kind with ``stats`` (``models.stats_tail``; PR 30).
    One array is one device→host transfer.  ``xp=np`` packs on the host
    (the protocol audit's stub engine)."""
    parts = [xp.asarray(p, xp.int32).reshape(-1) for p in (tokens, *flags)]
    if tail is not None:
        parts.append(tail)
    return xp.concatenate(parts)


def peel_step(host, tokens: int, tail: int = 0):
    """The inverse of :func:`host_vector`: a step's ONE vector for the
    host peeled into ``(tokens, flags, tail)`` — the first ``tokens``
    values, the last ``tail`` (``InferenceEngine.stats_tail``) and what
    lies between: decode's ``truncated [slots]``, verify's ``n_emit``
    then ``truncated`` (``flags.reshape(2, slots)``), nothing for a
    prefill.  Slices of ``host`` as it is given — a numpy array the host
    has read, a device array, a tracer inside a jitted loop that feeds
    the tokens back."""
    end = host.shape[0] - tail
    return host[:tokens], host[tokens:end], host[end:]
