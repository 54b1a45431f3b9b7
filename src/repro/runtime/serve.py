"""The compiled programs of one (batch, seq) serving bucket.

``compile_bucket`` AOT-compiles a bucket's cache initialiser, its prefill
(one pass for models with ``prefill``; the decode step once per prompt
token, ``_step_loop``, for the recurrent families) and its decode step.
The prefill and the step take the KV/state cache donated and update it
in place.  A multi-device ``mesh`` routes every matmul of the traced
forward through the plan engine (``planned_scope``:
``repro.plan.planned_matmuls``), so the programs execute solver-derived
``SchedulePlan``s instead of the local GSPMD baseline.  With per-row position ``offsets`` (models with
``supports_position_offsets``) a left-padded row decodes at its own
logical positions, its padding masked out of attention.

``batch_requests`` left-pads a request list into a bucket's batch and
``_sample`` draws the next token.  ``repro.serve.Server`` is the decode
loop over these programs: bucket routing, warmup and latency accounting.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec


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


def planned_scope(mesh, strategy: Optional[str] = None, tuning=None):
    """The plan-routing scope a bucket's programs trace under: route through
    ``planned_matmuls(mesh, strategy, tuning)`` when a multi-device mesh is
    given, otherwise a null context (the local GSPMD baseline path)."""
    if mesh is not None and getattr(mesh, "size", 1) > 1:
        from repro.plan import planned_matmuls

        return planned_matmuls(mesh, strategy, tuning)
    return contextlib.nullcontext()


def _prefill_fn(model, mesh, strategy, tuning):
    """The prefill as the jitted function ``prefill``.

    The plan scope is entered INSIDE the traced function, not around the
    call: JAX's trace cache is keyed on the traced callable,
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

    Returns ``(batch, lens)``: ``lens[i]`` is request i's true length, and
    ``batch.shape[1] - lens`` the row's position offset, so padded rows
    decode at their own logical positions.  An empty request list yields
    an explicit empty (0, 0) batch.  ``pad_to`` pads the
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
