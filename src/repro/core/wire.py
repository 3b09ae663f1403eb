"""Multi-process EASTER deployment: parties as separate OS processes.

The SPMD path (core/easter_lm.py) fuses all parties into one program — the
right thing on a TPU pod a single org operates. In an actual VFL deployment
the parties are separate *trust domains*: this module runs each passive
party in its own process, exchanging ONLY the protocol messages of Alg. 1
over pipes (public keys, blinded embeddings, predictions, loss signals).
The active party never receives raw embeddings or features.

    from repro.core.wire import WireEaster
    sys = WireEaster(arches, n_features, n_classes)
    sys.start(); sys.train(batches); sys.stop()

With ``mask_mode="int8"`` every embedding-/logit-shaped leg ships as
packed Z_2^8 ring words (4 bytes of payload per int32 word + one fp32
scale): the blinded uplink is agreed under a per-round dynamic scale via
a two-phase exchange (each party reveals only the SCALAR max|E_k|, the
active party broadcasts the resulting scale, parties reply with
quantized+masked words), and the downlink / prediction / loss-grad legs
are plain dynamic-int8 codecs with a per-leg scale in the frame.

Used by examples/wire_protocol_demo.py and tests/test_wire.py.
"""
from __future__ import annotations

import multiprocessing as mp
from typing import Dict, List, Optional, Tuple

import numpy as np


def _encode_leg(x) -> Tuple[np.ndarray, tuple, float]:
    """Frame one unmasked wire leg as packed int8 ring words + scale.

    Single-sender legs (C=1 in the ring_scale headroom), so the round
    can never wrap; the clip is a guard, not a semantic."""
    from repro.core import blinding

    x = np.asarray(x, np.float32)
    amax = float(np.max(np.abs(x))) if x.size else 0.0
    scale = float(blinding.ring_scale(amax, 1, "int8"))
    q = np.clip(np.round(x * scale), -127, 127).astype(np.int8)
    return blinding.pack_int8_words(q), x.shape, scale


def _decode_leg(words, shape, scale: float) -> np.ndarray:
    from repro.core import blinding

    q = blinding.unpack_int8_words(np.asarray(words), shape)
    return q.astype(np.float32) / np.float32(scale)


def _passive_party_main(conn, party_idx: int, arch_bytes, n_features: int,
                        lr: float, seed: int, mask_mode: str = "float"):
    """Subprocess entry: owns its features' model + secret key. Speaks only
    the wire protocol; raw data and parameters never leave this process.

    The party runs on the host CPU: ``JAX_PLATFORMS=cpu`` is set before
    JAX is imported here, so a party process never claims an accelerator
    that the parent process (the active party) holds. The config update
    covers a spawn parent whose main module already imported JAX into
    this child; no backend has started by then."""
    import os
    import pickle

    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from repro.core import blinding
    from repro.core.party_models import decide_fn, embed_fn, init_party
    from repro.optim import make_optimizer

    arch = pickle.loads(arch_bytes)
    params = init_party(jax.random.PRNGKey(seed), arch, n_features)
    opt = make_optimizer("adam", lr)
    opt_state = opt.init(params)
    kp = blinding.keygen(_test_seed=seed * 977 + 13)
    pair_seeds: Dict[int, int] = {}
    my_idx = party_idx            # index among passive parties (0-based)
    C = None
    state = {"E": None, "vjp_e": None, "vjp_d": None, "x": None,
             "round": 0}

    @jax.jit
    def embed_and_vjp(p, x):
        return jax.vjp(lambda pp: embed_fn(pp, arch, x), p)[0]

    while True:
        msg = conn.recv()
        cmd = msg[0]
        if cmd == "pubkey":
            conn.send(("pubkey", kp.pk))
        elif cmd == "setup":
            _, other_pks, C = msg
            for j, pk in other_pks.items():
                ck = blinding.shared_key(kp.sk, pk)
                pair_seeds[j] = blinding.prf_seed(ck)
        elif cmd == "embed":
            _, x_np, round_idx = msg
            x = jnp.asarray(x_np)
            E, vjp_e = jax.vjp(lambda pp: embed_fn(pp, arch, x), params)
            mask = jnp.zeros_like(E)
            for j, seed_j in pair_seeds.items():
                # full-63-bit-seed PRF shared with the SPMD paths: both
                # ends of a pair must derive the identical array for
                # cancellation across trust domains
                m = blinding.pair_mask(seed_j, E.shape, round_idx)
                mask = mask + (m if my_idx < j else -m)
            state["E"], state["vjp_e"] = E, vjp_e
            conn.send(("blinded_embed", np.asarray(E + mask)))
        elif cmd == "embed_amax":
            # int8 phase 1: embed locally, reveal ONLY the scalar
            # max|E_k| so the active party can agree the round's scale
            _, x_np, round_idx = msg
            x = jnp.asarray(x_np)
            E, vjp_e = jax.vjp(lambda pp: embed_fn(pp, arch, x), params)
            state["E"], state["vjp_e"] = E, vjp_e
            state["round"] = round_idx
            conn.send(("amax", float(jnp.max(jnp.abs(E)))))
        elif cmd == "embed_q":
            # int8 phase 2: quantize under the broadcast scale, add the
            # int8 ring masks, ship packed words (THE wire payload)
            _, scale = msg
            E = state["E"]
            round_idx = state["round"]
            q = np.asarray(blinding.quantize_ring(E, "int8", scale),
                           np.int8).astype(np.int64)
            for j, seed_j in pair_seeds.items():
                m = np.asarray(blinding.pair_mask(
                    seed_j, E.shape, round_idx, "int8")).astype(np.int64)
                q = q + (m if my_idx < j else -m)
            words = blinding.pack_int8_words(q.astype(np.int8))
            conn.send(("blinded_embed_q", words, tuple(E.shape)))
        elif cmd == "predict":
            if mask_mode == "int8":
                _, words, shape, scale = msg
                E_glob_np = _decode_leg(words, shape, scale)
            else:
                _, E_glob_np = msg
            Eg = jnp.asarray(E_glob_np)
            R, vjp_d = jax.vjp(
                lambda pp, e: decide_fn(pp, arch, e), params, Eg)
            state["vjp_d"] = vjp_d
            if mask_mode == "int8":
                conn.send(("prediction_q",) + _encode_leg(np.asarray(R)))
            else:
                conn.send(("prediction", np.asarray(R)))
        elif cmd == "grad":
            # active party's loss assist: dL_k/dR_k
            if mask_mode == "int8":
                _, words, shape, scale = msg
                gR_np = _decode_leg(words, shape, scale)
            else:
                _, gR_np = msg
            g_dec, gE = state["vjp_d"](jnp.asarray(gR_np))
            (g_emb,) = state["vjp_e"](gE / C)
            import jax as _j
            grads = _j.tree.map(lambda a, b: a + b, g_dec, g_emb)
            nonlocal_params, nonlocal_state = opt.update(grads, opt_state,
                                                         params)
            params, opt_state = nonlocal_params, nonlocal_state
            conn.send(("updated", True))
        elif cmd == "eval":
            _, x_np, E_glob_np = msg
            R = decide_fn(params, arch, jnp.asarray(E_glob_np))
            conn.send(("logits", np.asarray(R)))
        elif cmd == "stop":
            conn.send(("bye", None))
            return


class WireEaster:
    """Active-party orchestrator for the multi-process protocol."""

    def __init__(self, arches, n_features: List[int], n_classes: int,
                 lr: float = 1e-3, seed: int = 0,
                 record_transcript: bool = False,
                 mask_mode: str = "float"):
        import jax
        import pickle

        from repro.core.party_models import init_party
        from repro.optim import make_optimizer

        assert mask_mode in ("float", "int8"), mask_mode
        self.mask_mode = mask_mode
        self.arches = arches
        self.C = len(arches)
        self.K = self.C - 1
        self.n_classes = n_classes
        self._pickle = pickle
        # active party's own model (index 0)
        self.params = init_party(jax.random.PRNGKey(seed), arches[0],
                                 n_features[0])
        self.opt = make_optimizer("adam", lr)
        self.opt_state = self.opt.init(self.params)
        self.n_features = n_features
        self.lr = lr
        self.seed = seed
        self.conns = []
        self.procs = []
        # security audit hook: every payload the ACTIVE party observes on
        # the wire, as (direction, kind, round, party, np.ndarray). The
        # trust argument is that nothing here is a raw E_k
        # (tests/test_wire.py checks it against out-of-band recomputation).
        self.record_transcript = record_transcript
        self.transcript: List[Tuple[str, str, int, int, np.ndarray]] = []

    def _record(self, direction: str, kind: str, round_idx: int,
                party: int, payload):
        if self.record_transcript:
            self.transcript.append(
                (direction, kind, round_idx, party,
                 np.array(payload, copy=True)))

    def start(self):
        ctx = mp.get_context("spawn")
        for k in range(self.K):
            parent, child = ctx.Pipe()
            p = ctx.Process(
                target=_passive_party_main,
                args=(child, k, self._pickle.dumps(self.arches[k + 1]),
                      self.n_features[k + 1], self.lr, self.seed + k + 1,
                      self.mask_mode),
                daemon=True)
            p.start()
            self.conns.append(parent)
            self.procs.append(p)
        # key ceremony: collect public keys, redistribute
        pks = {}
        for k, c in enumerate(self.conns):
            c.send(("pubkey",))
            _, pk = c.recv()
            pks[k] = pk
        for k, c in enumerate(self.conns):
            others = {j: pk for j, pk in pks.items() if j != k}
            c.send(("setup", others, self.C))

    def _finish_int8_uplink(self, E_a, round_idx: int) -> np.ndarray:
        """int8 steps 1b-2: collect scalar amaxes, broadcast the agreed
        per-round scale, collect packed ring words, ring-aggregate.

        The transcript records the PACKED WORDS — the literal wire
        payload — plus the scalar amax each party reveals (the only
        non-masked statistic the narrow-ring mode leaks)."""
        import jax.numpy as jnp

        from repro.core import aggregation, blinding

        amaxes = [c.recv()[1] for c in self.conns]
        for k, a in enumerate(amaxes):
            self._record("passive->active", "embed_amax", round_idx,
                         k + 1, np.float32(a))
        amax = max([float(np.max(np.abs(np.asarray(E_a))))] + amaxes)
        scale = float(blinding.ring_scale(amax, self.C, "int8"))
        for c in self.conns:
            c.send(("embed_q", scale))
        q_rows = [blinding.quantize_ring(jnp.asarray(E_a), "int8", scale)]
        for k, c in enumerate(self.conns):
            _, words, shape = c.recv()
            self._record("passive->active", "blinded_embed", round_idx,
                         k + 1, words)
            q_rows.append(jnp.asarray(
                blinding.unpack_int8_words(words, shape)))
        E = aggregation.aggregate_int8_blinded(jnp.stack(q_rows), scale)
        return np.asarray(E, np.float32)

    def round(self, xs: List[np.ndarray], y: np.ndarray, round_idx: int):
        """One Alg. 1 round. xs: per-party feature arrays (party 0 first)."""
        import jax
        import jax.numpy as jnp

        from repro.core.losses import softmax_xent
        from repro.core.party_models import decide_fn, embed_fn

        # step 1: parallel local embeddings (passives return blinded)
        cmd = "embed_amax" if self.mask_mode == "int8" else "embed"
        for k, c in enumerate(self.conns):
            c.send((cmd, np.asarray(xs[k + 1]), round_idx))
        E_a, vjp_ea = jax.vjp(
            lambda pp: embed_fn(pp, self.arches[0], jnp.asarray(xs[0])),
            self.params)
        # step 2: secure aggregation (masks cancel in the sum)
        if self.mask_mode == "int8":
            E = self._finish_int8_uplink(E_a, round_idx)
        else:
            blinded = [c.recv()[1] for c in self.conns]
            for k, b in enumerate(blinded):
                self._record("passive->active", "blinded_embed", round_idx,
                             k + 1, b)
            E = (np.asarray(E_a) + sum(blinded)) / self.C
        # step 3: parties predict from the global embedding
        if self.mask_mode == "int8":
            frame = _encode_leg(E)
            for c in self.conns:
                c.send(("predict",) + frame)
            self._record("active->passive", "global_embed", round_idx, 0,
                         frame[0])
        else:
            for c in self.conns:
                c.send(("predict", E))
            self._record("active->passive", "global_embed", round_idx, 0, E)
        R_a, vjp_da = jax.vjp(
            lambda pp, e: decide_fn(pp, self.arches[0], e), self.params,
            jnp.asarray(E))
        if self.mask_mode == "int8":
            R_passive = []
            for k, c in enumerate(self.conns):
                _, words, shape, scale = c.recv()
                self._record("passive->active", "prediction", round_idx,
                             k + 1, words)
                R_passive.append(_decode_leg(words, shape, scale))
        else:
            R_passive = [c.recv()[1] for c in self.conns]
            for k, r in enumerate(R_passive):
                self._record("passive->active", "prediction", round_idx,
                             k + 1, r)
        # step 4: loss assist — active computes dL_k/dR_k for every party
        y_j = jnp.asarray(y)
        losses = []
        for k, (c, R_k) in enumerate(zip(self.conns, R_passive)):
            L_k, gR = jax.value_and_grad(
                lambda r: softmax_xent(r, y_j))(jnp.asarray(R_k))
            losses.append(float(L_k))
            if self.mask_mode == "int8":
                frame = _encode_leg(np.asarray(gR))
                c.send(("grad",) + frame)
                self._record("active->passive", "loss_grad", round_idx,
                             k + 1, frame[0])
            else:
                c.send(("grad", np.asarray(gR)))
                self._record("active->passive", "loss_grad", round_idx,
                             k + 1, np.asarray(gR))
        # step 5: active party's own update
        L_a, gR_a = jax.value_and_grad(
            lambda r: softmax_xent(r, y_j))(R_a)
        g_dec, gE = vjp_da(gR_a)
        (g_emb,) = vjp_ea(gE / self.C)
        grads = jax.tree.map(lambda a, b: a + b, g_dec, g_emb)
        self.params, self.opt_state = self.opt.update(
            grads, self.opt_state, self.params)
        for c in self.conns:
            c.recv()                       # updated acks
        return [float(L_a)] + losses

    def evaluate(self, xs, y) -> np.ndarray:
        import jax.numpy as jnp

        from repro.core.party_models import decide_fn, embed_fn

        cmd = "embed_amax" if self.mask_mode == "int8" else "embed"
        for k, c in enumerate(self.conns):
            c.send((cmd, np.asarray(xs[k + 1]), 10 ** 6))
        E_a = embed_fn(self.params, self.arches[0], jnp.asarray(xs[0]))
        if self.mask_mode == "int8":
            E = self._finish_int8_uplink(E_a, 10 ** 6)
        else:
            blinded = [c.recv()[1] for c in self.conns]
            E = (np.asarray(E_a) + sum(blinded)) / self.C
        accs = []
        R_a = decide_fn(self.params, self.arches[0], jnp.asarray(E))
        accs.append(float((np.argmax(np.asarray(R_a), -1) == y).mean()))
        for c in self.conns:
            c.send(("eval", None, E))
        for c in self.conns:
            R_k = c.recv()[1]
            accs.append(float((np.argmax(R_k, -1) == y).mean()))
        return np.asarray(accs)

    def stop(self):
        for c in self.conns:
            try:
                c.send(("stop",))
                c.recv()
            except Exception:
                pass
        for p in self.procs:
            p.join(timeout=10)
            if p.is_alive():
                p.terminate()
