"""Typed serving & training surface for EASTER systems.

This is the ONE public entry layer over the fused engines:

  serving   ``build_decoder(sys, DecodeConfig) -> (prefill_fn, decode_fn)``
            operating on a (``ServeRequest``, ``DecodeState``) pair —
            R concurrent request lanes, per-lane PRF nonces, EOS
            early-exit (core/decode.decode_chunk). The continuous-
            batching scheduler on top is ``core/serving.ServingEngine``.
  training  ``build_trainer(sys, TrainConfig) -> Trainer`` wrapping
            ``train_loop.build_train_chunk`` / ``make_train_step`` so
            launchers stop hand-assembling (params, opt_state, step)
            carry tuples; heterogeneous per-party optimizer specs
            (``optim.parse_party_spec`` output) are part of the config.

The legacy positional signatures (``decode.serve_tokens``,
``decode.build_serve_tokens``, ``EasterLM.serve_tokens``) remain as
deprecation shims for one release; ``tools/check_deprecated.py`` lints
against new internal callers.

Lane lifecycle (see docs/ARCHITECTURE.md "serving tier"):

  init_decode_state: every lane idle (``done=True`` — an idle lane is
  indistinguishable from a finished one: zero uplink, pad output, frozen
  cache). ``prefill_fn`` admits a request into a lane: a fresh B=1
  per-lane prefill of ``prompt[:-1]`` is spliced into the lane's cache
  row, the last prompt token becomes the lane's next input (the exact
  single-stream convention), and the lane's pos/nonce/key/budget are
  armed. ``decode_fn`` then advances EVERY live lane one protocol round
  per token — one blinded aggregation amortized over all concurrent
  requests — until the chunk ends or all lanes finish.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import blinding
from repro.core import decode as decode_mod
from repro.core import train_loop


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ServeRequest:
    """One generation request (immutable; host-side).

    ``tokens``: the full prompt (>= 2 ids — the last one is consumed as
    the first decode input, as in the single-stream drivers).
    ``eos_id``: -1 disables EOS early-exit for this request.
    ``temperature``: 0.0 = greedy; > 0 = per-lane categorical sampling.
    ``nonce``: per-request PRF nonce (< ``blinding.MAX_SERVE_NONCE``);
    None = the scheduler assigns a unique one at admission.
    """
    tokens: Tuple[int, ...]
    max_new_tokens: int
    eos_id: int = -1
    temperature: float = 0.0
    nonce: Optional[int] = None

    def __post_init__(self):
        object.__setattr__(self, "tokens", tuple(int(t)
                                                 for t in self.tokens))
        if len(self.tokens) < 2:
            raise ValueError("ServeRequest needs >= 2 prompt tokens "
                             "(the last one is the first decode input)")
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if self.nonce is not None and not (
                0 <= self.nonce <= blinding.MAX_SERVE_NONCE):
            raise ValueError(
                f"nonce {self.nonce} outside [0, "
                f"{blinding.MAX_SERVE_NONCE}] — the serve PRF span")


@dataclass(frozen=True)
class DecodeConfig:
    """Compile-time shape of the decoder a ``build_decoder`` call builds.

    ``lanes``: R, the number of concurrent decode slots.
    ``max_len``: per-lane KV ring-buffer slot length (prompt + generation
    must fit; a request's effective budget is capped to it).
    ``chunk``: decode rounds per compiled dispatch — the scheduling
    quantum: freed lanes are refilled between chunks (1 = per-token
    admission at per-token dispatch cost).
    ``base_key``: per-request sampling keys are
    ``fold_in(PRNGKey(base_key), nonce)`` — reproducible per request,
    independent across requests.
    """
    lanes: int
    max_len: int
    chunk: int = 8
    pad_id: int = 0
    window_override: int = -1
    base_key: int = 0
    donate: bool = True


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["tok", "caches", "pos", "key", "done", "remaining",
                 "nonce", "temp", "eos"],
    meta_fields=[])
@dataclass(frozen=True)
class DecodeState:
    """Device-resident per-lane decode state (a pytree; R = lanes).

    ``tok`` (R, 1) next input token; ``caches`` per-party per-lane KV
    (``init_caches(per_lane=True)``); ``pos`` (R,) sequence positions;
    ``key`` (R, 2) per-lane sampling keys; ``done`` (R,) lane frozen
    (idle OR finished — both emit zero uplink and pad tokens);
    ``remaining`` (R,) token budget left; ``nonce`` (R,) per-request PRF
    nonces; ``temp`` (R,) sampling temperatures; ``eos`` (R,) per-request
    EOS ids (-1 = none).
    """
    tok: Any
    caches: Any
    pos: Any
    key: Any
    done: Any
    remaining: Any
    nonce: Any
    temp: Any
    eos: Any


def init_decode_state(sys, cfg: DecodeConfig) -> DecodeState:
    """All-idle lane state (every lane done; admit via ``prefill_fn``)."""
    R = cfg.lanes
    return DecodeState(
        tok=jnp.full((R, 1), cfg.pad_id, jnp.int32),
        caches=sys.init_caches(R, cfg.max_len, cfg.window_override,
                               per_lane=True),
        pos=jnp.zeros((R,), jnp.int32),
        key=jnp.zeros((R, 2), jnp.uint32),
        done=jnp.ones((R,), bool),
        remaining=jnp.zeros((R,), jnp.int32),
        nonce=jnp.zeros((R,), jnp.int32),
        temp=jnp.zeros((R,), jnp.float32),
        eos=jnp.full((R,), -1, jnp.int32))


def build_decoder(sys, cfg: DecodeConfig):
    """The typed serving surface: ``(prefill_fn, decode_fn)``.

    ``prefill_fn(params, state, request, lane, *, nonce=None) -> state``
      admits ``request`` (a ``ServeRequest``) into decode slot ``lane``:
      one jitted B=1 prefill (cached per prompt length) spliced into the
      lane's cache row, lane metadata armed. ``nonce`` overrides
      ``request.nonce`` (the scheduler's assignment); one of the two must
      be set and be unique per in-flight request.

    ``decode_fn(params, state) -> (tokens (R, chunk), state, steps_run)``
      one fused lane-batched chunk (``decode.build_decode_chunk``): every
      live lane advances a token per protocol round, EOS/budget freezes
      lanes mid-chunk, the whole dispatch cuts off early when all lanes
      are done.

    Both donate ``state`` when ``cfg.donate`` — rebind it to the return.
    """
    seeds = sys.mask_seeds()
    wo = cfg.window_override

    def _prefill_into(params, state, prompt, lane, nonce, max_new, eos,
                      temp):
        # fresh per-lane B=1 prefill of prompt[:-1] at full slot length,
        # then splice the whole cache row over the freed lane (stacked
        # cache leaves all carry the lane axis at position 1)
        P = prompt.shape[1]
        c1 = sys.init_caches(1, cfg.max_len, wo, per_lane=True)
        _, c1 = sys.prefill(params, prompt[:, :P - 1], c1,
                            window_override=wo, seeds=seeds,
                            round_idx=nonce)
        caches = jax.tree.map(
            lambda big, one: jax.lax.dynamic_update_slice(
                big, one, (jnp.int32(0), lane) + (0,) * (one.ndim - 2)),
            state.caches, c1)
        key_r = jax.random.fold_in(
            jax.random.PRNGKey(cfg.base_key), nonce)
        return dataclasses.replace(
            state,
            tok=state.tok.at[lane].set(prompt[0, P - 1:]),
            caches=caches,
            pos=state.pos.at[lane].set(P - 1),
            key=state.key.at[lane].set(key_r),
            done=state.done.at[lane].set(False),
            remaining=state.remaining.at[lane].set(max_new),
            nonce=state.nonce.at[lane].set(nonce),
            temp=state.temp.at[lane].set(temp),
            eos=state.eos.at[lane].set(eos))

    prefill_cache: Dict[int, Any] = {}

    def prefill_fn(params, state, request: ServeRequest, lane,
                   *, nonce=None):
        nonce = request.nonce if nonce is None else nonce
        if nonce is None:
            raise ValueError("no nonce: set ServeRequest.nonce or pass "
                             "nonce= (the scheduler's assignment)")
        prompt = jnp.asarray(request.tokens, jnp.int32)[None, :]
        P = prompt.shape[1]
        if P > cfg.max_len:
            raise ValueError(f"prompt ({P}) exceeds the lane KV slot "
                             f"({cfg.max_len})")
        fn = prefill_cache.get(P)
        if fn is None:
            fn = jax.jit(_prefill_into,
                         donate_argnums=(1,) if cfg.donate else ())
            prefill_cache[P] = fn
        # budget capped to the slot: the lane must not write past max_len
        budget = min(request.max_new_tokens, cfg.max_len - P + 1)
        return fn(params, state, prompt,
                  jnp.asarray(lane, jnp.int32),
                  jnp.asarray(nonce, jnp.int32),
                  jnp.asarray(budget, jnp.int32),
                  jnp.asarray(request.eos_id, jnp.int32),
                  jnp.asarray(request.temperature, jnp.float32))

    decode_fn = decode_mod.build_decode_chunk(
        sys, cfg.chunk, pad_id=cfg.pad_id, donate_state=cfg.donate)

    return prefill_fn, decode_fn


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class TrainConfig:
    """Everything a launcher used to hand-assemble around the train step.

    ``optimizer``: a name (homogeneous, global-norm clipped by
    ``grad_clip``) or a prebuilt ``Optimizer``-shaped object.
    ``party_optimizers``: ``optim.parse_party_spec`` output
    (``{party: (name, lr, hparams)}``) — the paper's §IV-E heterogeneous
    per-party optimization; unlisted parties fall back to
    ``optimizer``/``lr``, listed parties clip per-party (default clip
    ``grad_clip`` unless the spec overrides).
    ``chunk``: optimizer steps per compiled dispatch (fused scan,
    ``train_loop.build_train_chunk``); 1 = jitted step-at-a-time driver
    (the A/B oracle) behind the same ``Trainer.run`` interface.
    """
    optimizer: Any = "adam"
    lr: float = 1e-3
    grad_clip: float = 1.0
    chunk: int = 8
    party_optimizers: Optional[Mapping[int, Tuple]] = None
    donate: bool = True


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["params", "opt_state", "step"], meta_fields=[])
@dataclass(frozen=True)
class TrainState:
    """(params, optimizer state, global step) as one pytree."""
    params: Any
    opt_state: Any
    step: Any


class Trainer:
    """Chunked training behind one ``run`` call — no carry tuples.

    ``init(params) -> TrainState``; ``run(state, batches) ->
    (TrainState, metrics)`` advances one chunk (``len(batches)`` steps,
    ONE dispatch when ``cfg.chunk > 1``) with ``state.step`` as the
    TRAIN-domain PRF round base. ``state`` is donated when configured —
    rebind to the returned one. ``metrics``: ``{"loss": (N,),
    "per_party": (N, C)}``.
    """

    def __init__(self, sys, cfg: TrainConfig):
        from repro import optim
        self.sys = sys
        self.cfg = cfg
        if cfg.party_optimizers:
            spec = {int(k): (v[0], v[1], dict(v[2]) if len(v) > 2 and v[2]
                             else {})
                    for k, v in cfg.party_optimizers.items()}
            for _, _, hp in spec.values():
                # listed parties clip like unlisted ones unless overridden
                hp.setdefault("grad_clip", cfg.grad_clip)
            base = (cfg.optimizer if isinstance(cfg.optimizer, str)
                    else "adam")
            self.opt = optim.make_party_optimizers(
                spec, sys.C,
                default=(base, cfg.lr, {"grad_clip": cfg.grad_clip}))
        elif callable(getattr(cfg.optimizer, "update", None)):
            self.opt = cfg.optimizer
        else:
            self.opt = optim.make_optimizer(cfg.optimizer, cfg.lr,
                                            grad_clip=cfg.grad_clip)
        self.chunk = max(1, cfg.chunk)
        if self.chunk > 1:
            self._chunk_fn = train_loop.build_train_chunk(
                sys, self.opt, donate=cfg.donate)
        else:
            self._step_fn = jax.jit(
                train_loop.make_train_step(sys, self.opt),
                donate_argnums=(0, 1) if cfg.donate else ())

    def init(self, params) -> TrainState:
        return TrainState(params=params, opt_state=self.opt.init(params),
                          step=jnp.zeros((), jnp.int32))

    def run(self, state: TrainState, batches):
        """One chunk: ``batches`` is a list of per-step batch dicts."""
        with obs.span("train.chunk", steps=len(batches)):
            n = len(batches)
            step0 = jnp.asarray(state.step, jnp.int32)
            if self.chunk > 1:
                with obs.span("train.stack_batches"):
                    stacked = train_loop.stack_batches(batches)
                with obs.span("train.dispatch"):
                    params, opt_state, step, metrics = self._chunk_fn(
                        state.params, state.opt_state, stacked, step0)
                return TrainState(params, opt_state, step), metrics
            params, opt_state = state.params, state.opt_state
            losses, pers = [], []
            for j, batch in enumerate(batches):
                batch = {k: jnp.asarray(v) for k, v in batch.items()}
                with obs.span("train.dispatch"):
                    params, opt_state, m = self._step_fn(
                        params, opt_state, batch, step0 + j)
                losses.append(m["loss"])
                pers.append(m["per_party"])
            metrics = {"loss": jnp.stack(losses),
                       "per_party": jnp.stack(pers)}
            return TrainState(params, opt_state, step0 + n), metrics


def build_trainer(sys, cfg: TrainConfig) -> Trainer:
    """Mirror of ``build_decoder`` on the training side."""
    return Trainer(sys, cfg)
