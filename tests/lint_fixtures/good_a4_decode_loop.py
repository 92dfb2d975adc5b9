"""Known-good A4 (ISSUE 13 decode-loop idiom): the multi-step decode
scan's trip count is PROVABLY bounded under the 512-iteration wedge
cap — `min(k, <=512)` resolves through the clamp even though `k`
itself is a runtime value (the committed
`models/paged.py::decode_multi` idiom), and small static
aranges/lengths pass. Data-driven scan lengths (no static bound at
all) stay un-flagged by design — XLA scans over sequence lengths are
normal; A4's wedge class is the statically huge trip count."""
import jax
import jax.numpy as jnp

_DECODE_TRIP_CAP = 512


def decode_loop_scan(body, carry, k_steps):
    # the committed multi-decode idiom: K rides the program key, the
    # inline clamp makes the bound lint-provable
    return jax.lax.scan(
        body, carry, jnp.arange(min(int(k_steps), 512), dtype=jnp.int32))


def decode_loop_length(body, carry, k_steps):
    return jax.lax.scan(body, carry, None,
                        length=min(k_steps, _DECODE_TRIP_CAP))


def decode_loop_fori(body, carry, k_steps):
    return jax.lax.fori_loop(0, min(int(k_steps), 64), body, carry)


def decode_loop_small_static(body, carry):
    return jax.lax.scan(body, carry, jnp.arange(16))


def decode_loop_clamped_span(body, carry, k_steps):
    # two-arg arange: exact lower endpoint + clamped stop stays provable
    return jax.lax.scan(body, carry,
                        jnp.arange(0, min(k_steps, _DECODE_TRIP_CAP)))


def decode_loop_clamped_lower(body, carry, start):
    # a min()-CLAMPED LOWER endpoint proves nothing about hi - lo
    # (start could be 0 at runtime): the linter must skip, not pass a
    # fabricated small trip count
    return jax.lax.scan(body, carry, jnp.arange(min(start, 4000), 4096))
