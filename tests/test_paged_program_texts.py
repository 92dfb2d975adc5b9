"""The paged programs of the families that keep ONE layer group (Llama's,
Kimi's) lower to the text they lowered to before layer groups came to the
cache (PR 35): a change to `models/paged.py`, the allocator or the
engine's block tables for a model with a windowed group adds no operation,
no operand and no output to theirs.

`tests/data/paged_program_texts.json` holds the SHA-256 of each program's
StableHLO text at toy widths, written from a `git archive` of the commit
it names:

    cd <a checkout of that commit> && JAX_PLATFORMS=cpu python \
        <this file> > <this repo>/tests/data/paged_program_texts.json

A PR that MEANS to change one of these programs writes the file anew from
its own tree and says so. Under another jax than the file's the texts are
not comparable and the test skips."""
import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data", "paged_program_texts.json")
B, P, S, K = 2, 4, 16, 2
CASES = [(family, kind, kv) for family, kinds, kvs in (
    ("llama", ("chunk", "decode", "multi_decode", "verify"), (None, "int8")),
    ("kimi_k2", ("chunk", "decode"), (None,)))
    for kind in kinds for kv in kvs]


def _model(family):
    import paddle_tpu as paddle
    paddle.seed(0)
    if family == "llama":
        from paddle_tpu.models import LlamaForCausalLM, llama_tiny
        return LlamaForCausalLM(llama_tiny(
            vocab_size=128, hidden_size=128, intermediate_size=256,
            num_attention_heads=2, num_key_value_heads=1))
    from paddle_tpu.models.kimi_k2 import KimiK2ForCausalLM, kimi_k2_tiny
    return KimiK2ForCausalLM(kimi_k2_tiny(experts_held=8, expert_offset=8))


def _arguments(eng, kind):
    i32 = jnp.int32
    base = (eng._state,) + tuple(eng._cache_lists())
    key = eng._null_key
    rows, table = jnp.ones((B,), i32), jnp.zeros((B, P), i32)
    if kind == "chunk":
        return base + (jnp.zeros((1, S), i32), i32(0), i32(S),
                       jnp.zeros((P,), i32), key)
    if kind == "decode":
        return base + (jnp.zeros((B, 1), i32), table, rows, key)
    if kind == "verify":
        return base + (jnp.zeros((B, K + 1), i32), table, rows, rows, key)
    return base + (jnp.zeros((B,), i32), table, rows, rows,
                   jnp.full((B,), -1, i32), key)


def program_text(family, kind, kv_dtype) -> str:
    import paddle_tpu as paddle
    from paddle_tpu.serving import ServingEngine
    eng = ServingEngine(_model(family), num_pages=16, page_size=16,
                        max_batch_size=B, kv_dtype=kv_dtype,
                        decode_steps=K if kind == "multi_decode" else 1)
    build = {"chunk": lambda: eng._build_chunk(S, P),
             "decode": lambda: eng._build_decode(B, P),
             "multi_decode": lambda: eng._build_multi_decode(B, K, P),
             "verify": lambda: eng._build_verify(B, K, P)}[kind]
    try:
        with paddle.no_grad():
            return build().lower(*_arguments(eng, kind)).as_text()
    finally:
        eng.shutdown()


def _name(family, kind, kv_dtype) -> str:
    return f"{family}.{kind}.{kv_dtype or 'served'}"


def _digest(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()


def pytest_generate_tests(metafunc):
    if "case" in metafunc.fixturenames:
        metafunc.parametrize("case", CASES, ids=[_name(*c) for c in CASES])


def test_a_one_group_program_lowers_to_the_text_it_had(case):
    import pytest
    with open(DATA) as f:
        kept = json.load(f)
    if kept["jax"] != jax.__version__:
        pytest.skip(f"the texts were lowered under jax {kept['jax']}")
    assert _digest(program_text(*case)) == kept["sha256"][_name(*case)], (
        f"{_name(*case)} no longer lowers to the text of {kept['commit']}")


if __name__ == "__main__":
    sys.path.insert(0, os.getcwd())          # the checkout it is run from
    import subprocess
    commit = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                            capture_output=True, text=True).stdout.strip()
    print(json.dumps({
        "commit": os.environ.get("TEXTS_OF", commit), "jax": jax.__version__,
        "sha256": {_name(*c): _digest(program_text(*c)) for c in CASES}},
        indent=1))
