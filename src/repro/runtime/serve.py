"""Serving runtime: batched greedy/temperature decoding over the KV cache.

``generate`` drives model.decode_step with a single jit'd step (position is
a traced scalar, so one compile serves the whole generation).  Prompts are
consumed through the same step (teacher forcing) -- robust across every
model family here, including the recurrent ones whose prefill is the
recurrence itself.

Two serving-specific extensions over the seed version:

  * ``mesh=`` routes every matmul in the forward through the plan engine
    (``repro.plan.planned_matmuls``): decode executes solver-derived
    ``SchedulePlan``s -- cost-model-ranked (or pinned via ``strategy=``),
    memoized in the plan cache -- instead of the local GSPMD baseline.
  * ``lens=`` marks per-request true prompt lengths in a left-padded
    batch.  Models that support per-row position offsets
    (``supports_position_offsets``) then mask the padding slots out of
    attention and place real tokens at their logical positions, so a
    request decoded inside a bucket emits the same greedy tokens as it
    would alone (pinned by tests/test_serve.py).

``repro.serve.Server`` builds the production path on top of this module:
persistent compiled step functions, (batch, seq) bucket routing, AOT
plan-cache warmup, and latency accounting.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro import obs


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_new_tokens: int = 32
    temperature: float = 0.0     # 0 => greedy
    max_seq: int = 256

    def __post_init__(self):
        if self.max_new_tokens < 0:
            raise ValueError(
                f"max_new_tokens must be >= 0, got {self.max_new_tokens}")
        if self.max_seq <= 0:
            raise ValueError(f"max_seq must be > 0, got {self.max_seq}")
        if self.temperature < 0.0:
            raise ValueError(
                f"temperature must be >= 0, got {self.temperature}")

    def validate_prompt_len(self, sp: int) -> None:
        """The KV/state cache holds ``max_seq`` slots; a prompt of length
        ``sp`` plus ``max_new_tokens`` generated tokens must fit or decode
        would silently wrap/overrun the cache."""
        if sp + self.max_new_tokens > self.max_seq:
            raise ValueError(
                f"prompt length {sp} + max_new_tokens {self.max_new_tokens} "
                f"exceeds max_seq {self.max_seq}; raise max_seq or shorten "
                f"the request")


def generate(
    model, params, prompts: np.ndarray, cfg: ServeConfig,
    key: Optional[jax.Array] = None,
    *,
    mesh=None,
    strategy: Optional[str] = None,
    tuning=None,
    lens: Optional[np.ndarray] = None,
    prefill_fn=None,
    step_fn=None,
) -> np.ndarray:
    """prompts: (B, S_prompt) int32 -> (B, S_prompt + max_new_tokens).

    ``mesh`` routes the forward through ``planned_matmuls`` (see module
    docstring); ``strategy`` pins the schedule inside that scope;
    ``tuning`` (a ``repro.tune`` table or ``Tuner``) prices in-scope plans
    with measured kernel seconds.  ``lens``
    gives per-request true lengths of a left-padded batch; models with
    ``supports_position_offsets`` then decode each row at its own logical
    positions.  ``prefill_fn``/``step_fn`` inject persistent compiled
    functions (``repro.serve.Server``); by default fresh jit wrappers are
    built per call.
    """
    b, sp = prompts.shape
    if b == 0:
        return np.asarray(prompts)
    cfg.validate_prompt_len(sp)
    cache = model.init_cache(b, cfg.max_seq)
    key = key if key is not None else jax.random.PRNGKey(0)

    offsets = None
    if lens is not None and getattr(model, "supports_position_offsets", False):
        offsets = jnp.asarray(sp - np.asarray(lens), jnp.int32)

    tokens = jnp.asarray(prompts, jnp.int32)
    out = [tokens]
    scope = planned_scope(mesh, strategy, tuning)
    with scope:
        if prefill_fn is None:
            prefill_fn = _default_prefill(model, mesh, strategy, tuning)
        if step_fn is None:
            step_fn = _default_step(model, mesh, strategy, tuning)
        with obs.span("serve.prefill", batch=b, seq=sp):
            if offsets is not None:
                logits, cache = prefill_fn(params, cache, tokens, offsets)
            else:
                logits, cache = prefill_fn(params, cache, tokens)
        if cfg.max_new_tokens == 0:
            return np.asarray(tokens)
        cur = _sample(logits, cfg, key)
        out.append(cur[:, None])
        for t in range(sp, sp + cfg.max_new_tokens - 1):
            key, sub = jax.random.split(key)
            with obs.span("serve.decode_step", batch=b, pos=t):
                if offsets is not None:
                    logits, cache = step_fn(params, cache, cur[:, None],
                                            jnp.int32(t), offsets)
                else:
                    logits, cache = step_fn(params, cache, cur[:, None],
                                            jnp.int32(t))
            cur = _sample(logits, cfg, sub)
            out.append(cur[:, None])
    return np.asarray(jnp.concatenate(out, axis=1))


def planned_scope(mesh, strategy: Optional[str] = None, tuning=None):
    """The plan-routing scope ``generate`` decodes under: route through
    ``planned_matmuls(mesh, strategy, tuning)`` when a multi-device mesh is
    given, otherwise a null context (the local GSPMD baseline path)."""
    if mesh is not None and getattr(mesh, "size", 1) > 1:
        from repro.plan import planned_matmuls

        return planned_matmuls(mesh, strategy, tuning)
    return contextlib.nullcontext()


def _prefill_fn(model, mesh, strategy, tuning):
    """The prefill as the jitted function ``prefill``.

    The plan scope is (re-)entered INSIDE the traced function, not just
    around the call: JAX's trace cache is keyed on the traced callable,
    and equal bound methods (``model.prefill``) would share a jaxpr traced
    earlier WITHOUT the scope -- silently skipping plan routing.  A
    closure per (model, mesh, strategy, tuning) gets its own trace-cache
    entry and reads the contextvar while tracing."""
    def prefill(params, cache, tokens, offsets=None):
        with planned_scope(mesh, strategy, tuning):
            if offsets is not None:
                return model.prefill(params, cache, tokens, offsets)
            return model.prefill(params, cache, tokens)

    return prefill


def _step_fn(model, mesh, strategy, tuning, layouts=None):
    """The decode step as the jitted function ``step`` (scope as in
    ``_prefill_fn``); ``layouts`` as in ``DecoderLM.decode_step``."""
    kw = {} if layouts is None else {"layouts": layouts}

    def step(params, cache, tokens, pos, offsets=None):
        with planned_scope(mesh, strategy, tuning):
            if offsets is not None:
                return model.decode_step(params, cache, tokens, pos, offsets,
                                         **kw)
            return model.decode_step(params, cache, tokens, pos, **kw)

    return step


def _step_loop(step):
    """Teacher-forced prefill for models without ``prefill`` (the
    recurrent families): one ``step`` per prompt token."""
    def loop(params, cache, tokens):
        logits = None
        for t in range(tokens.shape[1]):
            logits, cache = step(params, cache, tokens[:, t : t + 1],
                                 jnp.int32(t))
        return logits, cache

    return loop


@functools.lru_cache(maxsize=None)
def _default_prefill(model, mesh=None, strategy: Optional[str] = None,
                     tuning=None):
    """Memoized per (model, mesh, strategy, tuning) prefill: one-pass for
    models with ``prefill`` (DecoderLM), the step loop otherwise.  Like
    ``_default_step`` it takes the cache donated."""
    if hasattr(model, "prefill"):
        return jax.jit(_prefill_fn(model, mesh, strategy, tuning),
                       donate_argnums=(1,))
    return _step_loop(_default_step(model, mesh, strategy, tuning))


@functools.lru_cache(maxsize=None)
def _default_step(model, mesh=None, strategy: Optional[str] = None,
                  tuning=None):
    """Memoized per (model, mesh, strategy, tuning) decode step.

    The cache argument is donated: the model updates it in place, and the
    buffers passed in are deleted by the call.  A caller rebinds its cache
    to the one returned and never reads the one it passed."""
    return jax.jit(_step_fn(model, mesh, strategy, tuning),
                   donate_argnums=(1,))


@dataclasses.dataclass(frozen=True)
class BucketPrograms:
    """One (batch, seq) bucket's compiled programs.  ``init_cache()`` makes
    a cache; the prefill and each decode step take it donated and return
    it updated in place, all in the default device format."""

    prefill: Callable
    step: Callable
    init_cache: Callable


def compile_bucket(model, params, batch: int, seq: int, max_seq: int, *,
                   offsets: bool, mesh=None, strategy: Optional[str] = None,
                   tuning=None) -> BucketPrograms:
    """AOT-compile a bucket's programs.  ``params`` may be arrays or
    ``jax.ShapeDtypeStruct``s; the cache, tokens and logits live where the
    parameters do, or replicated over ``mesh`` when it routes the matmuls.

    Every program keeps the cache in the default format: a program read
    back from JAX's persistent compilation cache returns its outputs in
    the default layout (jax 0.9, TPU and CPU), whatever format it was
    compiled for.  The prefill only writes the cache, which no format
    makes it copy; the step holds each stacked cache to that format
    through its layer loop, where XLA would otherwise relayout the whole
    stack around the loop to suit the attention."""
    if mesh is not None and getattr(mesh, "size", 1) > 1:
        home = NamedSharding(mesh, PartitionSpec())
    else:
        home = jax.tree.leaves(params)[0].sharding
    cache = jax.eval_shape(lambda: model.init_cache(batch, max_seq))
    rows = (jax.ShapeDtypeStruct((batch,), jnp.int32),) if offsets else ()

    def compiled(fn, *args):
        shardings = (home,) * (2 + len(args) + len(rows))
        return jax.jit(fn, donate_argnums=(1,), in_shardings=shardings,
                       out_shardings=(home, home)).lower(
                           params, cache, *args, *rows).compile()

    init = jax.jit(lambda: model.init_cache(batch, max_seq),
                   out_shardings=home).lower().compile()
    one_pass = hasattr(model, "prefill")
    layouts = (jax.tree.map(lambda f: f.layout, init.output_formats)
               if one_pass else None)
    step = compiled(_step_fn(model, mesh, strategy, tuning, layouts),
                    jax.ShapeDtypeStruct((batch, 1), jnp.int32),
                    jax.ShapeDtypeStruct((), jnp.int32))
    if one_pass:
        prefill = compiled(_prefill_fn(model, mesh, strategy, tuning),
                           jax.ShapeDtypeStruct((batch, seq), jnp.int32))
    else:
        prefill = _step_loop(step)
    return BucketPrograms(prefill, step, init)


def _sample(logits: jax.Array, cfg: ServeConfig, key) -> jax.Array:
    if cfg.temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits / cfg.temperature, axis=-1).astype(
        jnp.int32
    )


def batch_requests(
    prompt_list: Sequence[Sequence[int]], pad_id: int = 0,
    *, pad_to: Optional[int] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Left-pad a list of variable-length prompts into one (B, S) batch.

    Returns ``(batch, lens)``: ``lens[i]`` is request i's true length --
    pass it to ``generate(lens=...)`` so padded rows decode at their own
    logical positions.  An empty request list yields an explicit empty
    (0, 0) batch (generate returns it unchanged).  ``pad_to`` pads the
    sequence axis to a fixed width (the bucket router's seq bucket).
    """
    if not prompt_list:
        return (np.zeros((0, pad_to or 0), np.int32),
                np.zeros((0,), np.int32))
    maxlen = max(len(p) for p in prompt_list)
    if pad_to is not None:
        if pad_to < maxlen:
            raise ValueError(
                f"pad_to={pad_to} shorter than longest prompt ({maxlen})")
        maxlen = pad_to
    batch = np.full((len(prompt_list), maxlen), pad_id, np.int32)
    lens = np.zeros(len(prompt_list), np.int32)
    for i, pr in enumerate(prompt_list):
        if len(pr) == 0:
            raise ValueError(f"request {i} is empty; prompts need >= 1 token")
        batch[i, maxlen - len(pr):] = pr
        lens[i] = len(pr)
    return batch, lens
