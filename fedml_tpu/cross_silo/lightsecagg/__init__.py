"""LightSecAgg cross-silo runtime (the ``LSA`` federated optimizer).

Parity target: reference ``cross_silo/lightsecagg/`` (~950 LoC:
``lsa_fedml_server_manager.py``, ``lsa_fedml_client_manager.py``) over the
math of ``core/mpc/lightsecagg.py`` — So et al.'s one-shot
aggregate-mask reconstruction. Where Bonawitz SecAgg (the ``SA`` runtime)
needs a per-dropout Shamir reconstruction round, LightSecAgg decodes the
*aggregate* mask in one interpolation from any ``split_t + privacy_t``
surviving responses.

Per FL round r, client i:
  1. trains; computes q_i = quantize(n_i * delta_i), zero-padded so the
     field vector length divides ``split_t``;
  2. draws a fresh random mask z_i over GF(2^31-1) and Lagrange-encodes it
     into n coded sub-masks (``mask_encoding``), one per client;
  3. uploads (q_i + z_i mod p, n_i, {j: coded sub-mask for j}).
Server: picks the surviving set U1, routes each survivor j the sub-masks
{i in U1}; j replies with their field SUM (one addition — the "light"
part); the server interpolates sum_{i in U1} z_i from the first
``split_t + privacy_t`` responses, subtracts, de-quantizes, and advances
the round.

Confidentiality against the server: a one-time key phase distributes each
client's X25519 channel public key; every coded sub-mask is sealed for its
recipient with ChaCha20-Poly1305 under the pairwise ECDH key
(``core/mpc/channels.py``), so the server routes only ciphertext and — with
fewer than ``privacy_t + 1`` colluding clients — learns nothing about any
individual mask ``z_i`` beyond the aggregate it decodes.
"""

from __future__ import annotations

import logging
import threading
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from ...core.collectives import tree_flatten_to_vector, vector_to_tree_like
from ...core.distributed.communication.message import (Message, tree_to_wire,
                                                       wire_to_tree)
from ...core.distributed.fedml_comm_manager import FedMLCommManager
from ...core.mpc import P, dequantize, quantize
from ...core.mpc import channels
from ...core.mpc.lightsecagg import decode_aggregate_mask, mask_encoding

logger = logging.getLogger(__name__)
_P_I = int(P)


class LSAMessage:
    C2S_PUBLIC_KEY = "lsa_pk"          # one-time channel-key advertisement
    S2C_PUBLIC_KEYS = "lsa_pks"
    S2C_TRAIN = "lsa_train"
    C2S_MASKED = "lsa_masked"          # masked input + sealed coded sub-masks
    S2C_AGG_REQUEST = "lsa_agg_req"    # surviving set + routed sub-masks
    C2S_AGG_SHARE = "lsa_agg_share"    # sum of routed sub-masks
    S2C_FINISH = "lsa_finish"

    KEY_PK = "pk"
    KEY_PKS = "pks"
    KEY_MODEL = "model"
    KEY_ROUND = "round"
    KEY_MASKED = "masked"
    KEY_N = "n"
    KEY_ENCODED = "encoded"            # {str(j): sealed sub-mask for j}
    KEY_ROUTED = "routed"              # {str(i): sealed sub-mask from i}
    KEY_SURVIVING = "surviving"
    KEY_AGG = "agg"


def lsa_params(n_clients: int, privacy_t: int, threshold: int):
    """split_t such that any ``threshold`` survivors can decode:
    responses needed = split_t + privacy_t <= threshold."""
    split_t = max(threshold - privacy_t, 1)
    return split_t


def _refuse_wire_compression(args) -> None:
    """LightSecAgg cannot compose with the core/wire compressors: its
    field encoding maps negatives to ``p - |q|`` (full-field magnitudes
    that overflow any low-bit lane of ``secagg_compress_bits``), and the
    MDS-coded sub-masks split the UNPACKED ``d_pad`` vector into
    ``split_t`` chunks — packing would change the vector the coding is
    defined over. Per-client sparsification support sets additionally
    leak masked coordinates. Refused outright rather than silently
    ignored or corrupted."""
    for knob in ("secagg_compress_bits", "comm_compression"):
        if getattr(args, knob, None):
            raise ValueError(
                "%s=%r is incompatible with LightSecAgg (full-field "
                "negative encodings overflow low-bit lanes; sparsifier "
                "support sets leak masked coordinates)"
                % (knob, getattr(args, knob)))


class LSAClientManager(FedMLCommManager):
    def __init__(self, args, trainer, comm=None, rank: int = 1, size: int = 0,
                 backend: str = "INPROC"):
        super().__init__(args, comm, rank, size, backend)
        _refuse_wire_compression(args)
        self.trainer = trainer
        self.idx = rank - 1
        self.n_clients = size - 1
        self.privacy_t = int(getattr(args, "lsa_privacy_t", 1) or 1)
        thr = int(getattr(args, "lsa_threshold", 0) or 0)
        self.threshold = thr if thr > 0 else max(self.n_clients - 1, 2)
        self.split_t = lsa_params(self.n_clients, self.privacy_t,
                                  self.threshold)
        self.round_idx = 0
        # masks z_i and Lagrange coding noise are SECRET: OS entropy only —
        # a z drawn from the public random_seed config could simply be
        # regenerated by the server, unmasking every update
        self._rng = channels.secret_rng()
        self.enc_sk, self.enc_pk = channels.keygen()
        self.peer_publics: Dict[int, bytes] = {}

    def register_message_receive_handlers(self) -> None:
        self.register_message_receive_handler(LSAMessage.S2C_PUBLIC_KEYS,
                                              self.on_public_keys)
        self.register_message_receive_handler(LSAMessage.S2C_TRAIN,
                                              self.on_train)
        self.register_message_receive_handler(LSAMessage.S2C_AGG_REQUEST,
                                              self.on_agg_request)
        self.register_message_receive_handler(LSAMessage.S2C_FINISH,
                                              self.on_finish)

    def run(self) -> None:
        msg = Message(LSAMessage.C2S_PUBLIC_KEY, self.rank, 0)
        msg.add_params(LSAMessage.KEY_PK, self.enc_pk)
        self.send_message(msg)
        super().run()

    def on_public_keys(self, msg: Message) -> None:
        self.peer_publics = {int(k): bytes(v) for k, v in
                             msg.get(LSAMessage.KEY_PKS).items()}

    def on_train(self, msg: Message) -> None:
        self.round_idx = int(msg.get(LSAMessage.KEY_ROUND, 0))
        params = wire_to_tree(msg.get(LSAMessage.KEY_MODEL),
                              self.trainer.params_template)
        new_params, n, _ = self.trainer.train(params, self.idx,
                                              self.round_idx)
        delta = jax.tree_util.tree_map(
            lambda a, b: np.asarray(a) - np.asarray(b), new_params, params)
        vec = np.asarray(tree_flatten_to_vector(delta), np.float32)
        q = np.asarray(quantize(vec * np.float32(n))).astype(np.uint64)
        # pad so the mask length divides split_t
        d = len(q)
        d_pad = -(-d // self.split_t) * self.split_t
        q = np.pad(q, (0, d_pad - d))
        z = self._rng.randint(0, _P_I, size=d_pad).astype(np.uint64)
        masked = ((q + z) % _P_I).astype(np.uint32)
        enc = mask_encoding(z, self.n_clients, self.privacy_t, self.split_t,
                            self._rng)  # [n, d_pad // split_t]
        out = Message(LSAMessage.C2S_MASKED, self.rank, 0)
        out.add_params(LSAMessage.KEY_MASKED, masked)
        out.add_params(LSAMessage.KEY_N, float(n))
        # each coded sub-mask is sealed for its recipient — the server
        # routes ciphertext it cannot read (the aad binds sender, receiver
        # and round so blobs cannot be replayed across slots or rounds)
        out.add_params(LSAMessage.KEY_ENCODED, {
            str(j): channels.seal(
                self.enc_sk, self.peer_publics[j],
                enc[j].astype("<u4").tobytes(),
                aad=channels.pair_aad(self.idx, j,
                                      b"lsa-r%d" % self.round_idx))
            for j in range(self.n_clients)})
        self.send_message(out)

    def on_agg_request(self, msg: Message) -> None:
        routed: Dict[str, Any] = msg.get(LSAMessage.KEY_ROUTED)
        round_idx = int(msg.get(LSAMessage.KEY_ROUND, self.round_idx))
        acc = None
        for i, blob in routed.items():
            try:
                pt = channels.open_sealed(
                    self.enc_sk, self.peer_publics[int(i)], bytes(blob),
                    aad=channels.pair_aad(int(i), self.idx,
                                          b"lsa-r%d" % round_idx))
            except channels.DecryptError as e:
                # ANY failed blob poisons the sum: a partial sum is a wrong
                # Lagrange evaluation point and would silently corrupt the
                # server's one-shot decode. Refuse to respond; the server's
                # agg-phase timeout handles the missing share.
                logger.error("lsa client %d: sub-mask from %s failed "
                             "authentication (%s); not responding",
                             self.idx, i, e)
                return
            sub = np.frombuffer(pt, "<u4").astype(np.uint64)
            acc = sub if acc is None else (acc + sub) % _P_I
        if acc is None:
            return
        out = Message(LSAMessage.C2S_AGG_SHARE, self.rank, 0)
        out.add_params(LSAMessage.KEY_AGG, acc.astype(np.uint32))
        self.send_message(out)

    def on_finish(self, msg: Message) -> None:
        self.finish()


class LSAServerManager(FedMLCommManager):
    def __init__(self, args, global_params, eval_fn=None, comm=None,
                 rank: int = 0, size: int = 0, backend: str = "INPROC"):
        super().__init__(args, comm, rank, size, backend)
        _refuse_wire_compression(args)
        self.global_params = global_params
        self.eval_fn = eval_fn
        self.n_clients = size - 1
        self.round_num = int(getattr(args, "comm_round", 1))
        self.round_idx = 0
        self.privacy_t = int(getattr(args, "lsa_privacy_t", 1) or 1)
        thr = int(getattr(args, "lsa_threshold", 0) or 0)
        self.threshold = thr if thr > 0 else max(self.n_clients - 1, 2)
        self.split_t = lsa_params(self.n_clients, self.privacy_t,
                                  self.threshold)
        self.round_timeout = float(getattr(args, "round_timeout_s", 0) or 0)
        # liveness floor: even with round_timeout_s unset, a crashed or
        # non-responding peer must eventually abort the session instead of
        # deadlocking it.
        # 60s floor: a first round's cold jit compiles take tens of
        # seconds (PERF.md, chip_smoke's per-phase compile seconds); a 3x
        # leash on a tight operator timeout must not abort a healthy
        # session mid-compile
        self._leash_s = (max(3.0 * self.round_timeout, 60.0)
                         if self.round_timeout > 0 else 300.0)
        self._template_vec = np.asarray(
            tree_flatten_to_vector(global_params))
        self.publics: Dict[int, bytes] = {}
        self._keys_done = False
        self.masked: Dict[int, np.ndarray] = {}
        self.weights: Dict[int, float] = {}
        # owner -> {recipient: sealed blob} — opaque to the server
        self.encoded: Dict[int, Dict[str, Any]] = {}
        self.agg_shares: List = []
        self._surviving: List[int] = []
        self._phase = "collect"
        self._lock = threading.Lock()
        self._timer: Optional[threading.Timer] = None
        self.history: List[Dict[str, Any]] = []
        self.result: Optional[dict] = None

    def register_message_receive_handlers(self) -> None:
        self.register_message_receive_handler(LSAMessage.C2S_PUBLIC_KEY,
                                              self.on_public_key)
        self.register_message_receive_handler(LSAMessage.C2S_MASKED,
                                              self.on_masked)
        self.register_message_receive_handler(LSAMessage.C2S_AGG_SHARE,
                                              self.on_agg_share)

    def run(self) -> None:
        self.register_message_receive_handlers()
        # key-phase leash: one client crashing before its pk send must not
        # hang the session forever (rounds only arm timers once started)
        self._timer = threading.Timer(self._leash_s, self._on_setup_timeout)
        self._timer.daemon = True
        self._timer.start()
        self.com_manager.handle_receive_message()

    def _on_setup_timeout(self) -> None:
        with self._lock:
            if self._keys_done:
                return
            logger.error("lsa: only %d/%d public keys at setup timeout — "
                         "aborting session", len(self.publics),
                         self.n_clients)
            self._phase = "done"
            self.result = {"error": "lsa_setup_timeout"}
        for rank in range(1, self.n_clients + 1):
            self.send_message(Message(LSAMessage.S2C_FINISH, 0, rank))
        self.finish()

    def on_public_key(self, msg: Message) -> None:
        """One-time channel-key phase: first round starts once every
        client's public key is in (the sub-mask seals need them all).
        Duplicate advertisements (client retries) must not re-trigger the
        broadcast or restart the round mid-protocol."""
        with self._lock:
            if self._keys_done:
                return
            self.publics[msg.get_sender_id() - 1] = bytes(
                msg.get(LSAMessage.KEY_PK))
            if len(self.publics) < self.n_clients:
                return
            self._keys_done = True
            if self._timer is not None:
                self._timer.cancel()
                self._timer = None
        for rank in range(1, self.n_clients + 1):
            out = Message(LSAMessage.S2C_PUBLIC_KEYS, 0, rank)
            out.add_params(LSAMessage.KEY_PKS,
                           {str(k): v for k, v in self.publics.items()})
            self.send_message(out)
        self._start_round()

    def _start_round(self) -> None:
        with self._lock:
            self._phase = "collect"
            if self._timer is not None:
                self._timer.cancel()
            self._timer = threading.Timer(
                self._leash_s, self._on_collect_timeout,
                args=(self.round_idx,))
            self._timer.daemon = True
            self._timer.start()
        wire = tree_to_wire(self.global_params)
        for rank in range(1, self.n_clients + 1):
            out = Message(LSAMessage.S2C_TRAIN, 0, rank)
            out.add_params(LSAMessage.KEY_MODEL, wire)
            out.add_params(LSAMessage.KEY_ROUND, self.round_idx)
            self.send_message(out)

    def _on_collect_timeout(self, armed_round: int) -> None:
        with self._lock:
            if self._phase != "collect" or self.round_idx != armed_round:
                return
            if len(self.masked) < max(self.threshold,
                                      self.split_t + self.privacy_t):
                logger.error(
                    "lsa round %d: %d masked inputs < threshold %d at "
                    "timeout — aborting", self.round_idx, len(self.masked),
                    self.threshold)
                self._phase = "done"
                self.result = {"error": "lsa_below_threshold",
                               "round": self.round_idx}
                abort = True
            else:
                logger.warning(
                    "lsa round %d: proceeding with %d/%d survivors",
                    self.round_idx, len(self.masked), self.n_clients)
                self._begin_agg_locked()
                abort = False
        if abort:
            for rank in range(1, self.n_clients + 1):
                self.send_message(Message(LSAMessage.S2C_FINISH, 0, rank))
            self.finish()

    def on_masked(self, msg: Message) -> None:
        idx = msg.get_sender_id() - 1
        with self._lock:
            if self._phase != "collect":
                logger.warning("lsa: late masked input from %d ignored", idx)
                return
            self.masked[idx] = np.asarray(msg.get(LSAMessage.KEY_MASKED),
                                          np.uint32)
            self.weights[idx] = float(msg.get(LSAMessage.KEY_N))
            self.encoded[idx] = msg.get(LSAMessage.KEY_ENCODED)
            if len(self.masked) == self.n_clients:
                self._begin_agg_locked()
            elif self.round_timeout > 0 and len(self.masked) == 1:
                if self._timer is not None:
                    self._timer.cancel()
                self._timer = threading.Timer(
                    self.round_timeout, self._on_collect_timeout,
                    args=(self.round_idx,))
                self._timer.daemon = True
                self._timer.start()

    def _begin_agg_locked(self) -> None:
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None
        self._phase = "agg"
        self._surviving = sorted(self.masked)
        self.agg_shares = []
        # a survivor dying (or refusing a tampered blob) between masked
        # upload and agg response must not hang the decode phase either
        self._timer = threading.Timer(
            self._leash_s, self._on_agg_timeout, args=(self.round_idx,))
        self._timer.daemon = True
        self._timer.start()
        for j in self._surviving:
            out = Message(LSAMessage.S2C_AGG_REQUEST, 0, j + 1)
            out.add_params(LSAMessage.KEY_ROUND, self.round_idx)
            out.add_params(LSAMessage.KEY_SURVIVING,
                           [int(i) for i in self._surviving])
            out.add_params(LSAMessage.KEY_ROUTED,
                           {str(i): self.encoded[i][str(j)]
                            for i in self._surviving})
            self.send_message(out)

    def _on_agg_timeout(self, armed_round: int) -> None:
        with self._lock:
            if self._phase != "agg" or self.round_idx != armed_round:
                return
            logger.error(
                "lsa round %d: only %d/%d agg shares at timeout — decode "
                "impossible, aborting session", self.round_idx,
                len(self.agg_shares), self.split_t + self.privacy_t)
            self._phase = "done"
            self.result = {"error": "lsa_agg_timeout",
                           "round": self.round_idx}
        for rank in range(1, self.n_clients + 1):
            self.send_message(Message(LSAMessage.S2C_FINISH, 0, rank))
        self.finish()

    def on_agg_share(self, msg: Message) -> None:
        j = msg.get_sender_id() - 1
        need = self.split_t + self.privacy_t
        with self._lock:
            if self._phase != "agg":
                return
            self.agg_shares.append((j, np.asarray(
                msg.get(LSAMessage.KEY_AGG), np.uint32)))
            if len(self.agg_shares) < need:
                return
            if self._timer is not None:
                self._timer.cancel()
                self._timer = None
            self._phase = "decode"
        self._decode_and_advance()

    def _decode_and_advance(self) -> None:
        need = self.split_t + self.privacy_t
        responders = [j for j, _ in self.agg_shares[:need]]
        responses = [s.astype(np.uint64) for _, s in self.agg_shares[:need]]
        d = len(self._template_vec)
        d_pad = -(-d // self.split_t) * self.split_t
        z_sum = decode_aggregate_mask(responses, responders, self.n_clients,
                                      self.privacy_t, self.split_t, d_pad)
        total = np.zeros(d_pad, np.uint64)
        for i in self._surviving:
            total = (total + self.masked[i].astype(np.uint64)) % _P_I
        total = (total + _P_I - z_sum % _P_I) % _P_I
        vec = np.asarray(dequantize(total[:d].astype(np.uint32)))
        wsum = sum(self.weights[i] for i in self._surviving)
        agg_delta = vector_to_tree_like(
            (vec / max(wsum, 1e-12)).astype(np.float32), self.global_params)
        self.global_params = jax.tree_util.tree_map(
            lambda g, u: np.asarray(g) + np.asarray(u),
            self.global_params, agg_delta)
        rec: Dict[str, Any] = {"round": self.round_idx,
                               "survivors": len(self._surviving)}
        if self.eval_fn is not None:
            rec.update(self.eval_fn(self.global_params))
            logger.info("lsa round %d: %s", self.round_idx, rec)
        self.history.append(rec)
        with self._lock:
            self.masked.clear()
            self.weights.clear()
            self.encoded.clear()
            self.agg_shares = []
            self._surviving = []
            self.round_idx += 1
            done = self.round_idx >= self.round_num
            if done:
                self._phase = "done"
        if done:
            for rank in range(1, self.n_clients + 1):
                self.send_message(Message(LSAMessage.S2C_FINISH, 0, rank))
            last = next((r for r in reversed(self.history)
                         if "test_acc" in r), {})
            self.result = {"params": self.global_params,
                           "history": self.history,
                           "final_test_acc": last.get("test_acc"),
                           "rounds": self.round_num}
            self.finish()
            return
        self._start_round()


def run_lsa_inproc(args, fed, bundle, spec=None,
                   client_factory=None) -> Dict[str, Any]:
    """Server + N LightSecAgg clients as threads over the in-proc broker."""
    import threading as _threading

    from ...core.distributed.communication.inproc import InProcBroker
    from ...optimizers.registry import create_optimizer
    from ..client.trainer import SiloTrainer
    from ..horizontal.runner import _build_spec, _make_eval_fn

    broker = InProcBroker()
    args.inproc_broker = broker
    spec = _build_spec(fed, bundle, spec)
    n = int(getattr(args, "client_num_per_round", 2))
    rng = jax.random.PRNGKey(int(getattr(args, "random_seed", 0)))
    init_rng, _ = jax.random.split(rng)
    global_params = jax.device_get(bundle.init(init_rng, fed.train.x[0, 0]))
    server = LSAServerManager(args, global_params,
                              eval_fn=_make_eval_fn(spec, fed),
                              rank=0, size=n + 1, backend="INPROC")
    import copy
    inner_args = copy.copy(args)
    inner_args.federated_optimizer = "FedAvg"  # protocol rides plain FedAvg
    clients = []
    for r in range(1, n + 1):
        optimizer = create_optimizer(inner_args, spec)
        trainer = SiloTrainer(args, fed, bundle, spec, optimizer)
        if client_factory is not None:
            clients.append(client_factory(r, args, trainer))
        else:
            clients.append(LSAClientManager(args, trainer, rank=r,
                                            size=n + 1, backend="INPROC"))
    threads = [_threading.Thread(target=c.run, daemon=True) for c in clients]
    for t in threads:
        t.start()
    server.run()
    for t in threads:
        t.join(timeout=30.0)
    return server.result
