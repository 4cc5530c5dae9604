"""The one maker of the program digests the tests pin: a sha256 of the text
``jax.make_jaxpr`` prints, which carries no scope name and no source line,
so a refactor that keeps every program keeps every string. The tables stay
beside the tests that read them (tests/test_ouro.py, tests/test_wide_rows.py,
tests/test_pipeline.py); a PR that changes a program on purpose makes them
again with one command, which prints every table as its file spells it:

    PYTHONPATH=. python tests/program_digests.py

It also reads the Pallas kernels out of a traced program (``pallas_calls``,
``kernel_matmuls``, ``kernels_digest``: tests/test_flash_attention.py pins
the kernels by themselves, whatever calls them).

CPU, tiny, traced only: nothing is compiled."""

import hashlib
import re

import conftest  # the suite's devices and threefry setting, before jax's
import jax
import jax.numpy as jnp

from mingpt_distributed_tpu.config import GPTConfig
from mingpt_distributed_tpu.models import generate as gen
from mingpt_distributed_tpu.models import gpt
from mingpt_distributed_tpu.serving.engine import DecodeEngine


def sha(jaxprs) -> str:
    """Of the jaxprs' text less what differs between two processes: objects'
    addresses, and the order a ``frozenset`` (a ``shard_map``'s manual axes)
    prints its members in."""
    text = re.sub(r"0x[0-9a-f]+", "", "\n".join(map(str, jaxprs)))
    text = re.sub(r"frozenset\(\{([^}]*)\}\)", lambda m: "frozenset({%s})"
                  % ", ".join(sorted(m.group(1).split(", "))), text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub)


def pallas_calls(jaxpr, name=None):
    return [eqn for eqn in _equations(getattr(jaxpr, "jaxpr", jaxpr))
            if eqn.primitive.name == "pallas_call"
            and name in (None, eqn.params["name"])]


def kernel_matmuls(jaxpr, name):
    """``dot_general``s in each ``pl.when`` body of the Pallas kernels named
    ``name`` in a (closed) jaxpr: one list a call, the bodies in the
    kernel's own order, bodies without a matmul left out."""
    matmuls = lambda jaxpr: sum(
        e.primitive.name == "dot_general" for e in _equations(jaxpr))
    calls = []
    for call in pallas_calls(jaxpr, name):
        bodies = [matmuls(branch.jaxpr) for e in call.params["jaxpr"].eqns
                  if e.primitive.name == "cond"
                  for branch in e.params["branches"]]
        calls.append([n for n in bodies if n])
    return calls


def kernels_digest(jaxpr):
    """sha256 (tests/program_digests.sha) of a program's Pallas kernels and
    nothing around them: each call's name, body, grid and block shapes and
    its index maps."""
    calls = pallas_calls(jaxpr)
    assert calls
    return sha([part for call in calls for part in (
        [call.params["name"], call.params["jaxpr"],
         call.params["grid_mapping"]]
        + [bm.index_map_jaxpr
           for bm in call.params["grid_mapping"].block_mappings])])


def _abstract_params(cfg: GPTConfig):
    return jax.eval_shape(lambda: gpt.init(jax.random.key(0), cfg))


def _ids(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def forward_jaxpr(cfg: GPTConfig, mesh=None, train: bool = False,
                  tokens=(2, 16), loss: bool = False):
    """``gpt.forward`` over (B, T) ``tokens``; ``train``: with a dropout key
    for an argument and ``deterministic=False``; ``mesh``: as the trainer
    hands it one; ``loss``: the trainer's ``value_and_grad`` of the loss-only
    forward, the tokens their own targets (the chunked loss and both its
    rules; no other pinned program holds them)."""
    key = (jax.random.key(0),) if train else ()
    forward = lambda p, t, *rng: gpt.forward(
        p, t, cfg, rng=rng[0] if rng else None, deterministic=not train,
        mesh=mesh, **(dict(targets=t, return_logits=False) if loss else {}))
    if loss:
        forward = jax.value_and_grad(lambda *a, f=forward: f(*a)[1])
    return jax.make_jaxpr(forward)(
        _abstract_params(cfg), _ids(*tokens), *key)


def forward_digest(cfg: GPTConfig, **how) -> str:
    return sha([forward_jaxpr(cfg, **how)])


def cached_digests(cfg: GPTConfig):
    """(``gpt.forward`` with the cached forward of a chunk at a scalar
    offset, the cached forward of a decode step at a position a lane), over a
    3-lane cache with the counters a serving pool carries."""
    params = _abstract_params(cfg)
    cache = jax.eval_shape(lambda: dict(
        gen.init_cache(cfg, 3),
        **{name: make(cfg) for name, make in (
            (gen.MOE_ROWS, gen.init_moe_rows),
            (gen.SPARSE_ROWS, gen.init_sparse_rows))
           if make(cfg) is not None}))
    cached = lambda p, t, c, o: gen._forward_cached(
        p, t, c, o, cfg, valid=jnp.ones(t.shape, bool))
    return (sha([forward_jaxpr(cfg),
                 jax.make_jaxpr(cached)(params, _ids(3, 8), cache, _ids())]),
            sha([jax.make_jaxpr(cached)(params, _ids(3, 1), cache, _ids(3))]))


def engine_digest(cfg: GPTConfig, decode: bool) -> str:
    """The engine's own decode program, or its prefill programs, over a
    3-slot pool. The decode program is traced with the eleven arguments
    PR 39's had: without the step's tokens and their mask (PR 43: the last
    two, one ``select`` at the program's head; tests/test_run_ahead.py
    holds that it is all they add)."""
    engine = DecodeEngine(
        jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype),
                     _abstract_params(cfg)), cfg,
        n_slots=3, prefill_buckets=(8, 16))
    return sha([jitted.trace(*args[:11], **kwargs).jaxpr
                for name, _, jitted, args, kwargs in engine.programs()
                if (name == "decode") == decode])


def _print_tables():
    """Every table, as its file spells it, made on this tree."""
    import pytest

    import test_ouro
    import test_pipeline
    import test_wide_rows as wide

    def table(name, rows):
        print(f"{name} = {{")
        for key, value in rows:
            print(f"    {key!r}: {value!r},".replace("'", '"'))
        print("}")

    print("# tests/test_ouro.py")
    table("DIGESTS", ((arch, cached_digests(GPTConfig.make(**sizes)))
                      for arch, sizes in sorted(test_ouro.BEFORE.items())))
    print("# tests/test_wide_rows.py")
    of = lambda form: wide.model(form)[0]
    table("PARENT_PREFILL_DIGESTS", (
        (form, engine_digest(of(form), decode=False))
        for form in wide.PARENT_PREFILL_DIGESTS))
    table("DECODE_DIGESTS", ((form, engine_digest(of(form), decode=True))
                             for form in wide.DECODE_DIGESTS))
    with pytest.MonkeyPatch.context() as patch:
        conftest.walk_in_blocks_with(patch)(8)
        table("WALKED_DECODE_DIGESTS", (
            (form, engine_digest(of(form), decode=True))
            for form in wide.WALKED_DECODE_DIGESTS))
    table("PARENT_FORWARD_DIGESTS", (
        (key, wide.forward_digest_of(key))
        for key in wide.PARENT_FORWARD_DIGESTS))
    with pytest.MonkeyPatch.context() as patch:
        wide.per_head(patch)
        table("the per-head pairs", (
            (form, (engine_digest(of(form), decode=False),
                    engine_digest(of(form), decode=True)))
            for form in ("mha", "gqa-rope")))
    print("# tests/test_pipeline.py")
    table("PIPELINE_FORWARD_DIGESTS", (
        (case, test_pipeline.pipeline_digest(case, jax.devices()[:8]))
        for case in test_pipeline.PIPELINE_FORWARD_DIGESTS))


if __name__ == "__main__":
    _print_tables()
