"""Secure-aggregation cross-silo runtime (the ``SA`` federated optimizer).

Parity target: reference ``cross_silo/secagg/`` (~1.4k LoC:
``sa_fedml_server_manager.py``, ``sa_fedml_client_manager.py``,
``sa_message_define.py``) — the Bonawitz-style protocol driven through extra
WAN message rounds: advertise keys -> share secrets -> masked input ->
unmask. Field math (p = 2^31 - 1, uint32 lanes; SURVEY §7 requantization
note) lives in ``core/mpc``; this module is the FSM.

Bonawitz et al. is a PER-AGGREGATION protocol: every FL round runs its own
key advertisement + secret sharing with FRESH mask material. Reusing one
key set across rounds (as earlier revisions here did) is unsound — a
client that survives round r (its self-seed legitimately reconstructed)
and drops in round r' (its mask key legitimately reconstructed) has handed
the server both masks of round r, i.e. its round-r individual update. So,
per FL round r:

  train -> C2S_ROUND_PK   (fresh X25519 mask key + fresh 128-bit self-seed)
        -> S2C_ROUND_PKS  (the round cohort = clients that advertised)
        -> C2S_SHARES     (Shamir shares of self-seed limbs + mask-key
                           limbs, AEAD-sealed per recipient, AAD-bound to
                           (sender, receiver, round))
        -> S2C_ROUTED     (mask cohort = clients whose shares arrived)
        -> C2S_MASKED     masked_k = quantize(n_k * delta_k)
                            + PRG(b_k) + sum_{j>k} PRG(s_kj)
                            - sum_{j<k} PRG(s_jk)   over the mask cohort
        -> S2C_UNMASK_REQUEST / C2S_UNMASK_SHARES -> aggregate.

Dropout recovery at every phase: the server proceeds with the >= threshold
respondents of each phase (the cohort shrinks monotonically within a
round); a client dropping after the share phase is recovered by
reconstructing its mask key from Shamir shares and cancelling its residual
pairwise masks. Clients wipe a round's secrets after answering its unmask
request, and answer at most once per round.

Confidentiality against the server: each client holds a session-scoped
X25519 *channel* keypair (``core/mpc/channels.py``) that seals routed
shares with ChaCha20-Poly1305 under per-pair keys — the server relays only
ciphertext (``test_secagg_runtime.py`` asserts the relayed bytes reveal no
share and fail AEAD authentication under any other pair's key). The
per-round *mask* keypairs seed the pairwise PRG masks via real ECDH; mask
secrets and 128-bit self-seeds are Shamir-shared as 24-bit limbs over
GF(2^31-1). At unmask time survivors reveal exactly what Bonawitz
prescribes — dropped clients' mask-key shares OR survivors' self-seed
shares, never both for one index: overlapping surviving/dropped lists (the
active-server attack) are refused outright.
"""

from __future__ import annotations

import logging
import threading
from typing import Any, Dict, List, Optional

import jax
import msgpack
import numpy as np

from ...core.distributed.communication.message import (Message, tree_to_wire,
                                                       wire_to_tree)
from ...core.distributed.fedml_comm_manager import FedMLCommManager
from ...core.collectives import (tree_flatten_to_vector, vector_to_tree_like)
from ...core.mpc import (P, dequantize, expand_mask, quantize,
                         shamir_reconstruct, shamir_share)
from ...core.mpc import channels
from ...core.wire import (LanePlan, field_encode, lane_dequantize_sum,
                          plan_for, record_update_stages, suggest_scale)

logger = logging.getLogger(__name__)
_P_I = int(P)


def _round_tag(round_idx: int) -> bytes:
    """AAD domain tag binding sealed share blobs to one FL round — a blob
    recorded in round r fails authentication if replayed in round r'."""
    return b"sa-round-%d" % int(round_idx)


def _refuse_sparsified_wire(args) -> None:
    """Masked summation needs every client on the same dense coordinate
    set — a per-client top-k/rand-k support set would leak exactly the
    coordinates masking hides AND misalign the mod-p sums. Lane
    quantization (``secagg_compress_bits``) is the SecAgg-compatible
    compression path; sparsifiers are refused outright."""
    if getattr(args, "comm_compression", None):
        raise ValueError(
            "comm_compression=%r cannot compose with SecAgg: per-client "
            "sparsification support sets leak masked coordinates and "
            "break masked-sum alignment. Use secagg_compress_bits "
            "(4|8|16-bit field lanes) instead."
            % getattr(args, "comm_compression"))


def _checked_threshold(args, n_clients: int) -> int:
    """Shamir threshold, enforced > n/2. The per-request overlap guard only
    sees ONE request; with t <= n/2 a deviating server could give disjoint
    halves of the cohort split views (i 'surviving' to one half, 'dropped'
    to the other) and still collect >= t shares of BOTH of i's secrets.
    t > n/2 makes the two >= t responder sets intersect, and the
    intersection client would have had to answer both views — which the
    once-per-round response guard forbids."""
    t = int(getattr(args, "secagg_threshold", 0) or 0)
    if not t:
        return max(2, n_clients // 2 + 1)
    if t <= n_clients // 2:
        raise ValueError(
            f"secagg_threshold={t} is <= n/2 for {n_clients} clients; a "
            f"majority threshold (>= {n_clients // 2 + 1}) is required to "
            "block split-view active-server attacks")
    return t


class SAMessage:
    # session setup (channel keys only — transport encryption)
    C2S_CHANNEL_PK = "sa_cpk"
    S2C_CHANNEL_PKS = "sa_cpks"
    # per-round protocol
    S2C_TRAIN = "sa_train"
    C2S_ROUND_PK = "sa_round_pk"
    S2C_ROUND_PKS = "sa_round_pks"
    C2S_SHARES = "sa_shares"
    S2C_ROUTED_SHARES = "sa_routed"
    C2S_MASKED_MODEL = "sa_masked"
    S2C_UNMASK_REQUEST = "sa_unmask_req"
    C2S_UNMASK_SHARES = "sa_unmask_shares"
    S2C_FINISH = "sa_finish"

    KEY_PK = "pk"
    KEY_PKS = "pks"
    KEY_COHORT = "cohort"
    KEY_SHARES = "shares"
    KEY_MODEL = "model"
    KEY_MASKED = "masked"
    KEY_N = "n"
    KEY_ROUND = "round"
    KEY_SURVIVING = "surviving"
    KEY_DROPPED = "dropped"
    KEY_SEED_SHARES = "seed_shares"
    KEY_KEY_SHARES = "key_shares"
    # lane-compressed field quantization (core/wire, ISSUE 19): the train
    # broadcast carries {bits, k_max, scale} when secagg_compress_bits is
    # on; absent otherwise (dense field vectors, byte-identical wire)
    KEY_WIRE = "wire"


class SecAggClientManager(FedMLCommManager):
    """Client side: channel-key setup once, then per round
    (train -> fresh keys -> share -> mask -> unmask-assist)."""

    def __init__(self, args, trainer, comm=None, rank: int = 1, size: int = 0,
                 backend: str = "INPROC"):
        super().__init__(args, comm, rank, size, backend)
        self.trainer = trainer
        self.n_clients = int(getattr(args, "client_num_per_round", size - 1))
        _refuse_sparsified_wire(args)
        self.threshold = _checked_threshold(args, self.n_clients)
        self.idx = self.rank - 1  # client index 0..n-1
        # ALL secret material comes from OS entropy, never from the public
        # random_seed config (the server holds the same args and could
        # regenerate anything derived from it)
        self._rng = channels.secret_rng()
        # session-scoped channel keypair: seals routed shares; never shared
        self.enc_sk, self.enc_pk = channels.keygen()
        self.peer_enc: Dict[int, bytes] = {}  # peer_idx -> channel pk
        self.round_idx = 0
        self._round: Optional[Dict[str, Any]] = None  # this round's secrets
        self._responded_rounds: set = set()
        # lane compression (core/wire): error-feedback residual carrying
        # this client's quantization + clip error across rounds. Committed
        # only when the masked vector is actually SENT — a round sat out
        # (not in the cohort) must not advance the residual for mass that
        # was never shipped.
        self._ef_residual: Optional[np.ndarray] = None

    def register_message_receive_handlers(self) -> None:
        h = self.register_message_receive_handler
        h(SAMessage.S2C_CHANNEL_PKS, self.on_channel_pks)
        h(SAMessage.S2C_TRAIN, self.on_train)
        h(SAMessage.S2C_ROUND_PKS, self.on_round_pks)
        h(SAMessage.S2C_ROUTED_SHARES, self.on_routed_shares)
        h(SAMessage.S2C_UNMASK_REQUEST, self.on_unmask_request)
        h(SAMessage.S2C_FINISH, self.on_finish)

    def run(self) -> None:
        msg = Message(SAMessage.C2S_CHANNEL_PK, self.rank, 0)
        msg.add_params(SAMessage.KEY_PK, self.enc_pk)
        self.send_message(msg)
        super().run()

    def on_channel_pks(self, msg: Message) -> None:
        self.peer_enc = {int(k): bytes(v)
                         for k, v in msg.get(SAMessage.KEY_PKS).items()}

    # -- per-round phases ---------------------------------------------------

    def on_train(self, msg: Message) -> None:
        self.round_idx = int(msg.get(SAMessage.KEY_ROUND, 0))
        params = wire_to_tree(msg.get(SAMessage.KEY_MODEL),
                              self.trainer.params_template)
        new_params, n, _ = self.trainer.train(params, self.idx,
                                              self.round_idx)
        delta = jax.tree_util.tree_map(
            lambda a, b: np.asarray(a) - np.asarray(b), new_params, params)
        vec = np.asarray(tree_flatten_to_vector(delta), np.float32)
        wire_cfg = msg.get(SAMessage.KEY_WIRE)
        residual_next = None
        if wire_cfg is not None:
            # lane-compressed field path (core/wire): EF-compensate, clip,
            # stochastically round into b-bit lanes and pack L per uint32 —
            # the masked vector shrinks by L while the masked SUM stays
            # bit-exact (lane headroom covers k_max summands below p).
            # Rounding randomness need not be secret; seeded per
            # (client, round) so sessions replay deterministically.
            plan = LanePlan.from_wire(wire_cfg)
            scale = float(wire_cfg["scale"])
            packed, residual_next = field_encode(
                vec * np.float32(n), scale, plan, self._ef_residual,
                np.random.default_rng(((self.idx + 1) << 20)
                                      ^ self.round_idx))
            q = packed.astype(np.uint64)
        else:
            q = np.asarray(quantize(vec * np.float32(n))).astype(np.uint64)
        # fresh mask material for THIS round only (see module docstring)
        mask_sk, mask_pk = channels.keygen()
        self._round = {
            "round": self.round_idx,
            "q": q, "n": float(n),
            "d_model": int(vec.shape[0]),
            "residual_next": residual_next,
            "mask_sk": mask_sk, "mask_pk": mask_pk,
            "self_seed": self._rng.randbits(channels.SEED_BITS),
            "pks": {}, "held": {},
        }
        out = Message(SAMessage.C2S_ROUND_PK, self.rank, 0)
        out.add_params(SAMessage.KEY_ROUND, self.round_idx)
        out.add_params(SAMessage.KEY_PK, mask_pk)
        self.send_message(out)

    def on_round_pks(self, msg: Message) -> None:
        r = self._round
        if r is None or int(msg.get(SAMessage.KEY_ROUND)) != r["round"]:
            return
        r["pks"] = {int(k): bytes(v)
                    for k, v in msg.get(SAMessage.KEY_PKS).items()}
        cohort = sorted(r["pks"])
        if self.idx not in cohort:
            logger.warning("secagg client %d: not in round %d cohort — "
                           "sitting this round out", self.idx, r["round"])
            self._round = None
            return
        # Shamir-share the 128-bit self-seed and the mask secret key, both
        # as 24-bit limbs (each limb its own Shamir instance over
        # GF(2^31-1)); the j-th share set is sealed FOR cohort member j
        # under the pairwise channel key and AAD-bound to this round — the
        # server routes only ciphertext it cannot open or replay.
        n_sh = len(cohort)
        seed_sh = [shamir_share(limb, n_sh, self.threshold, self._rng)
                   for limb in channels.int_to_limbs(r["self_seed"],
                                                     channels.SEED_LIMBS)]
        key_sh = [shamir_share(limb, n_sh, self.threshold, self._rng)
                  for limb in channels.key_to_limbs(r["mask_sk"])]
        out = Message(SAMessage.C2S_SHARES, self.rank, 0)
        out.add_params(SAMessage.KEY_ROUND, r["round"])
        sealed = {}
        for pos, j in enumerate(cohort):
            payload = msgpack.packb(
                [[list(ls[pos]) for ls in seed_sh],
                 [list(ls[pos]) for ls in key_sh]])
            sealed[str(j)] = channels.seal(
                self.enc_sk, self.peer_enc[j], payload,
                aad=channels.pair_aad(self.idx, j, _round_tag(r["round"])))
        out.add_params(SAMessage.KEY_SHARES, sealed)
        self.send_message(out)

    def on_routed_shares(self, msg: Message) -> None:
        r = self._round
        if r is None or int(msg.get(SAMessage.KEY_ROUND)) != r["round"]:
            return
        mask_cohort = [int(i) for i in msg.get(SAMessage.KEY_COHORT)]
        if self.idx not in mask_cohort:
            self._round = None
            return
        for k, blob in msg.get(SAMessage.KEY_SHARES).items():
            i = int(k)
            # the whole parse stays in the try: AEAD authenticates whatever
            # the SENDER sealed, so a malicious peer can deliver
            # authentically-sealed garbage — that must drop the share, not
            # kill the receive loop
            try:
                payload = channels.open_sealed(
                    self.enc_sk, self.peer_enc[i], bytes(blob),
                    aad=channels.pair_aad(i, self.idx,
                                          _round_tag(r["round"])))
                seed_shares, key_shares = msgpack.unpackb(payload)
            except (channels.DecryptError, ValueError, TypeError) as e:
                logger.warning("secagg client %d: dropping share from %d: "
                               "%s", self.idx, i, e)
                continue
            r["held"][i] = (seed_shares, key_shares)
        # mask and submit: pairwise masks over the mask cohort only
        q = r["q"]
        d = len(q)
        total = expand_mask(r["self_seed"], d).astype(np.uint64)
        for j in mask_cohort:
            if j == self.idx:
                continue
            s = channels.mask_seed(r["mask_sk"], r["pks"][j])
            m = expand_mask(s, d).astype(np.uint64)
            if self.idx < j:
                total = (total + m) % _P_I
            else:
                total = (total + _P_I - m) % _P_I
        masked = ((q + total) % _P_I).astype(np.uint32)
        out = Message(SAMessage.C2S_MASKED_MODEL, self.rank, 0)
        out.add_params(SAMessage.KEY_ROUND, r["round"])
        out.add_params(SAMessage.KEY_MASKED, masked)
        out.add_params(SAMessage.KEY_N, r["n"])
        # per-stage byte ledger: dense-equivalent vs post-mask field bytes
        record_update_stages(SAMessage.C2S_MASKED_MODEL,
                             raw=int(r["d_model"]) * 4,
                             masked=int(masked.nbytes))
        if r["residual_next"] is not None:
            # the quantized vector ships now — commit the EF residual
            self._ef_residual = r["residual_next"]
        self.send_message(out)

    def on_unmask_request(self, msg: Message) -> None:
        r = self._round
        rnd = int(msg.get(SAMessage.KEY_ROUND))
        if r is None or rnd != r["round"] or rnd in self._responded_rounds:
            logger.warning("secagg client %d: ignoring unmask request for "
                           "round %s (stale/duplicate)", self.idx, rnd)
            return
        surviving = [int(i) for i in msg.get(SAMessage.KEY_SURVIVING)]
        dropped = [int(i) for i in msg.get(SAMessage.KEY_DROPPED)]
        # Active-server guard (Bonawitz et al. §6.2): a server listing
        # client i as BOTH surviving and dropped would collect >= threshold
        # shares of i's self-mask seed AND mask secret key, strip both
        # masks from i's masked vector, and recover i's individual update.
        # Per-round fresh keys already confine any reveal to this round;
        # within the round, refuse overlapping lists outright.
        overlap = set(surviving) & set(dropped)
        if overlap:
            logger.error(
                "secagg client %d: REFUSING unmask request — clients %s "
                "listed as both surviving and dropped (active-server "
                "attack); aborting session", self.idx, sorted(overlap))
            self.finish()
            return
        out = Message(SAMessage.C2S_UNMASK_SHARES, self.rank, 0)
        out.add_params(SAMessage.KEY_ROUND, rnd)
        out.add_params(SAMessage.KEY_SEED_SHARES,
                       {str(i): r["held"][i][0] for i in surviving
                        if i in r["held"]})
        out.add_params(SAMessage.KEY_KEY_SHARES,
                       {str(i): r["held"][i][1] for i in dropped
                        if i in r["held"]})
        # answer once, then wipe this round's secrets (forward secrecy: a
        # later replayed/forged request can reveal nothing)
        self._responded_rounds.add(rnd)
        self._round = None
        self.send_message(out)

    def on_finish(self, msg: Message) -> None:
        self.finish()


class SecAggServerManager(FedMLCommManager):
    """Server side: per-round key/share routing, sums masked vectors mod p,
    runs the unmask round, dequantizes, applies the aggregated delta."""

    def __init__(self, args, global_params, eval_fn=None, comm=None,
                 rank: int = 0, size: int = 0, backend: str = "INPROC"):
        super().__init__(args, comm, rank, size, backend)
        self.global_params = global_params
        self.eval_fn = eval_fn
        self.n_clients = int(getattr(args, "client_num_per_round", size - 1))
        _refuse_sparsified_wire(args)
        self.threshold = _checked_threshold(args, self.n_clients)
        self.round_num = int(getattr(args, "comm_round", 1))
        self.round_timeout = float(getattr(args, "round_timeout_s", 0) or 0)
        self.round_idx = 0
        self.channel_pks: Dict[int, bytes] = {}
        # per-round state
        self.round_pks: Dict[int, bytes] = {}
        self.cohort: List[int] = []        # advertisers of this round
        self.share_matrix: Dict[int, Dict[str, Any]] = {}  # sealed blobs
        self.mask_cohort: List[int] = []   # share senders of this round
        self.masked: Dict[int, np.ndarray] = {}
        self.weights: Dict[int, float] = {}
        self.unmask_responses: Dict[int, Message] = {}
        self._surviving: List[int] = []
        self._dropped: List[int] = []
        self.history: List[Dict[str, Any]] = []
        self.result: Optional[dict] = None
        self._template_vec = np.asarray(
            tree_flatten_to_vector(global_params))
        # lane-compressed field quantization (core/wire): pack L b-bit
        # lanes per uint32 field element so the masked wire drops from
        # 4 B/coord to 4/L. k_max = the full client count — the lane
        # headroom must cover every summand the protocol could admit.
        bits = int(getattr(args, "secagg_compress_bits", 0) or 0)
        self._wire_plan: Optional[LanePlan] = None
        self._wire_scale = 0.0
        self._round_scale = 0.0
        if bits:
            self._wire_plan = plan_for(bits, self.n_clients)
            self._wire_scale = suggest_scale(
                float(getattr(args, "secagg_compress_clip", 4.0)),
                self._wire_plan)
        self._lock = threading.Lock()
        # setup -> (pk -> shares -> collect -> unmask -> aggregate)* -> done
        self._phase = "setup"
        self._timer: Optional[threading.Timer] = None
        # liveness floor: even with round_timeout_s unset, a crashed peer
        # must eventually abort the session instead of deadlocking it —
        # 60s floor: a first round's cold jit compiles take tens of
        # seconds (PERF.md, chip_smoke's per-phase compile seconds); a 3x
        # leash on a tight operator timeout must not abort a healthy
        # session mid-compile
        self._leash_s = (max(3.0 * self.round_timeout, 60.0)
                         if self.round_timeout > 0 else 300.0)

    def register_message_receive_handlers(self) -> None:
        h = self.register_message_receive_handler
        h(SAMessage.C2S_CHANNEL_PK, self.on_channel_pk)
        h(SAMessage.C2S_ROUND_PK, self.on_round_pk)
        h(SAMessage.C2S_SHARES, self.on_shares)
        h(SAMessage.C2S_MASKED_MODEL, self.on_masked_model)
        h(SAMessage.C2S_UNMASK_SHARES, self.on_unmask_shares)

    def run(self) -> None:
        # setup leash: a client crashing before its channel-pk send must
        # not hang the setup barrier forever
        self._arm_timer(self._leash_s, "setup")
        super().run()

    # -- timer plumbing -----------------------------------------------------

    def _arm_timer(self, seconds: float, phase: str) -> None:
        """(Re)arm the single phase timer. Caller may or may not hold the
        lock; threading.Timer start/cancel are thread-safe."""
        if self._timer is not None:
            self._timer.cancel()
        self._timer = threading.Timer(seconds, self._on_phase_timeout,
                                      args=(phase, self.round_idx))
        self._timer.daemon = True
        self._timer.start()

    def _abort(self, error: str, **extra) -> None:
        """Common abort: record the error, tell every client, stop."""
        with self._lock:
            self._phase = "done"
            self.result = {"error": error, "round": self.round_idx, **extra}
        for rank in range(1, self.n_clients + 1):
            self.send_message(Message(SAMessage.S2C_FINISH, 0, rank))
        self.finish()

    def _on_phase_timeout(self, phase: str, armed_round: int) -> None:
        """One handler for every phase leash: proceed with the >= threshold
        respondents of the phase, abort below threshold."""
        with self._lock:
            if self._phase != phase or self.round_idx != armed_round:
                return
            if phase == "setup":
                n, need = len(self.channel_pks), self.n_clients
                action = "abort"  # setup needs everyone (channel keys)
            elif phase == "pk":
                n, need = len(self.round_pks), self.threshold
                action = "pks" if n >= need else "abort"
            elif phase == "shares":
                n, need = len(self.share_matrix), self.threshold
                action = "route" if n >= need else "abort"
            elif phase == "collect":
                n, need = len(self.masked), self.threshold
                action = "unmask" if n >= need else "abort"
            elif phase == "unmask":
                n, need = len(self.unmask_responses), self.threshold
                action = "aggregate" if n >= need else "abort"
            else:
                return
            if action != "abort":
                logger.warning("secagg round %d: proceeding past phase %r "
                               "at timeout with %d respondents",
                               self.round_idx, phase, n)
                if action == "pks":
                    self._broadcast_round_pks_locked()
                elif action == "route":
                    self._route_shares_locked()
                elif action == "unmask":
                    self._begin_unmask_locked()
                elif action == "aggregate":
                    self._phase = "aggregate"
        if action == "abort":
            logger.error("secagg round %d: phase %r incomplete at timeout "
                         "(%d respondents < %d) — aborting session",
                         armed_round, phase, n, need)
            self._abort(f"secagg_{phase}_timeout")
        elif action == "aggregate":
            self._unmask_guarded()

    # -- session setup ------------------------------------------------------

    def on_channel_pk(self, msg: Message) -> None:
        with self._lock:
            if self._phase != "setup":
                return
            self.channel_pks[msg.get_sender_id() - 1] = bytes(
                msg.get(SAMessage.KEY_PK))
            if len(self.channel_pks) < self.n_clients:
                return
            self._phase = "pk"  # claimed; _start_round rebroadcasts state
        for rank in range(1, self.n_clients + 1):
            out = Message(SAMessage.S2C_CHANNEL_PKS, 0, rank)
            out.add_params(SAMessage.KEY_PKS,
                           {str(k): v for k, v in self.channel_pks.items()})
            self.send_message(out)
        self._start_round()

    # -- per-round phases ---------------------------------------------------

    def _start_round(self) -> None:
        with self._lock:
            self._phase = "pk"
            self.round_pks = {}
            self.cohort = []
            self.share_matrix = {}
            self.mask_cohort = []
            self.masked.clear()
            self.weights.clear()
            self.unmask_responses = {}
            self._surviving = []
            self._dropped = []
            self._arm_timer(self._leash_s, "pk")
        wire = tree_to_wire(self.global_params)
        wire_cfg = None
        if self._wire_plan is not None:
            # freeze this round's scale: every client must quantize with
            # the exact value the server will dequantize the sum with
            self._round_scale = float(self._wire_scale)
            wire_cfg = dict(self._wire_plan.to_wire(),
                            scale=self._round_scale)
        for rank in range(1, self.n_clients + 1):
            out = Message(SAMessage.S2C_TRAIN, 0, rank)
            out.add_params(SAMessage.KEY_MODEL, wire)
            out.add_params(SAMessage.KEY_ROUND, self.round_idx)
            if wire_cfg is not None:
                out.add_params(SAMessage.KEY_WIRE, wire_cfg)
            self.send_message(out)

    def on_round_pk(self, msg: Message) -> None:
        idx = msg.get_sender_id() - 1
        with self._lock:
            if (self._phase != "pk" or
                    int(msg.get(SAMessage.KEY_ROUND)) != self.round_idx):
                return
            self.round_pks[idx] = bytes(msg.get(SAMessage.KEY_PK))
            if len(self.round_pks) == self.n_clients:
                self._broadcast_round_pks_locked()
            elif self.round_timeout > 0 and len(self.round_pks) == 1:
                # first arrival (training time dominates this phase): swap
                # the dead-round leash for the tight straggler timer
                self._arm_timer(self.round_timeout, "pk")

    def _broadcast_round_pks_locked(self) -> None:
        """pk -> shares. Caller holds the lock."""
        self._phase = "shares"
        self.cohort = sorted(self.round_pks)
        self._arm_timer(self._leash_s, "shares")
        pks = {str(k): self.round_pks[k] for k in self.cohort}
        for j in self.cohort:
            out = Message(SAMessage.S2C_ROUND_PKS, 0, j + 1)
            out.add_params(SAMessage.KEY_ROUND, self.round_idx)
            out.add_params(SAMessage.KEY_PKS, pks)
            self.send_message(out)

    def on_shares(self, msg: Message) -> None:
        owner = msg.get_sender_id() - 1
        with self._lock:
            if (self._phase != "shares" or owner not in self.cohort or
                    int(msg.get(SAMessage.KEY_ROUND)) != self.round_idx):
                return
            self.share_matrix[owner] = msg.get(SAMessage.KEY_SHARES)
            if len(self.share_matrix) == len(self.cohort):
                self._route_shares_locked()

    def _route_shares_locked(self) -> None:
        """shares -> collect. Caller holds the lock. The mask cohort is the
        set whose shares arrived — only they mask and submit."""
        self._phase = "collect"
        self.mask_cohort = sorted(self.share_matrix)
        self._arm_timer(self._leash_s, "collect")
        for j in self.mask_cohort:
            routed = {str(i): self.share_matrix[i][str(j)]
                      for i in self.mask_cohort}
            out = Message(SAMessage.S2C_ROUTED_SHARES, 0, j + 1)
            out.add_params(SAMessage.KEY_ROUND, self.round_idx)
            out.add_params(SAMessage.KEY_COHORT, self.mask_cohort)
            out.add_params(SAMessage.KEY_SHARES, routed)
            self.send_message(out)

    def on_masked_model(self, msg: Message) -> None:
        idx = msg.get_sender_id() - 1
        with self._lock:
            if (self._phase != "collect" or idx not in self.mask_cohort or
                    int(msg.get(SAMessage.KEY_ROUND)) != self.round_idx):
                logger.warning("secagg: late/foreign masked input from "
                               "client %d ignored (phase=%s)", idx,
                               self._phase)
                return
            self.masked[idx] = np.asarray(msg.get(SAMessage.KEY_MASKED),
                                          np.uint32)
            self.weights[idx] = float(msg.get(SAMessage.KEY_N))
            if len(self.masked) == len(self.mask_cohort):
                self._begin_unmask_locked()
            elif self.round_timeout > 0 and len(self.masked) == 1:
                # first arrival: swap the dead-round leash for the tight
                # straggler timer
                self._arm_timer(self.round_timeout, "collect")

    def _begin_unmask_locked(self) -> None:
        """collect -> unmask. Caller holds self._lock."""
        self._phase = "unmask"
        self._surviving = sorted(self.masked)
        self._dropped = [i for i in self.mask_cohort
                         if i not in self.masked]
        self.unmask_responses = {}
        # a survivor dying between masked upload and unmask response must
        # not hang the session: proceed with >= threshold responses at the
        # leash, abort below threshold
        self._arm_timer(self._leash_s, "unmask")
        for rank in [i + 1 for i in self._surviving]:
            out = Message(SAMessage.S2C_UNMASK_REQUEST, 0, rank)
            out.add_params(SAMessage.KEY_ROUND, self.round_idx)
            out.add_params(SAMessage.KEY_SURVIVING, self._surviving)
            out.add_params(SAMessage.KEY_DROPPED, self._dropped)
            self.send_message(out)

    def on_unmask_shares(self, msg: Message) -> None:
        sender = msg.get_sender_id() - 1
        with self._lock:
            if (self._phase != "unmask" or sender not in self._surviving or
                    int(msg.get(SAMessage.KEY_ROUND)) != self.round_idx):
                return
            # key by sender: a duplicated response must not satisfy the
            # count early, and feeding the same Shamir x-coordinate twice
            # into Lagrange reconstruction silently yields a wrong secret
            # (duplicate x -> zero denominator -> pow(0, p-2) = 0)
            self.unmask_responses[sender] = msg
            if len(self.unmask_responses) < len(self._surviving):
                return  # wait for all surviving (simplest consistent point)
            if self._timer is not None:
                self._timer.cancel()
                self._timer = None
            self._phase = "aggregate"
        self._unmask_guarded()

    # -- reconstruction + aggregation ---------------------------------------

    def _collect_shares(self, key: str, idx: int) -> List[Any]:
        shares = []
        for resp in self.unmask_responses.values():
            sh = resp.get(key).get(str(idx))
            if sh is not None:
                shares.append(sh)
            if len(shares) >= self.threshold:
                break
        if len(shares) < self.threshold:
            raise RuntimeError(
                f"secagg: {len(shares)} shares < threshold {self.threshold} "
                f"for client {idx} ({key})")
        return shares

    def _reconstruct_limbs(self, key: str, idx: int,
                           n_limbs: int) -> List[int]:
        """Reconstruct a limb-shared wide secret for ``idx`` from the first
        >= threshold unmask responses under ``key`` (each 24-bit limb is
        its own Shamir instance over GF(2^31-1))."""
        per_resp = self._collect_shares(key, idx)
        return [shamir_reconstruct([tuple(resp[limb]) for resp in per_resp])
                for limb in range(n_limbs)]

    def _reconstruct_seed(self, idx: int) -> int:
        """Client ``idx``'s 128-bit self-mask seed from its limb shares."""
        return channels.limbs_to_int(self._reconstruct_limbs(
            SAMessage.KEY_SEED_SHARES, idx, channels.SEED_LIMBS))

    def _reconstruct_mask_key(self, idx: int):
        """Reconstruct client ``idx``'s X25519 mask secret from its 24-bit
        limb shares (each limb is its own Shamir instance)."""
        return channels.limbs_to_key(self._reconstruct_limbs(
            SAMessage.KEY_KEY_SHARES, idx, channels.KEY_LIMBS))

    def _unmask_guarded(self) -> None:
        """Run _unmask_and_advance, routing ANY failure to the abort path.
        _collect_shares can legitimately raise when the >= threshold
        responders happen not to hold >= threshold decryptable shares of
        some client (a peer's setup share failed AEAD and was dropped),
        and a byzantine responder can send structurally malformed shares
        (wrong limb count -> IndexError/TypeError). On the timer thread an
        escaping exception would kill the timer and wedge the session in
        'aggregate' with no leash armed — a deadlock instead of the
        intended abort."""
        try:
            self._unmask_and_advance()
        except Exception as e:
            logger.error("secagg round %d: unmask failed (%s) — aborting "
                         "session", self.round_idx, e)
            self._abort("secagg_unmask_failed", detail=str(e))

    def _unmask_and_advance(self) -> None:
        surviving = self._surviving
        d_model = len(self._template_vec)
        # with lanes on, the whole protocol (masks, Shamir-recovered mask
        # cancellation, the mod-p sum) runs over the PACKED length — both
        # sides derive masks from expand_mask(seed, d) with the same d
        d = (self._wire_plan.packed_len(d_model)
             if self._wire_plan is not None else d_model)
        total = np.zeros(d, np.uint64)
        for m in self.masked.values():
            total = (total + m.astype(np.uint64)) % _P_I
        # reconstruct each surviving client's self-mask seed and subtract
        for i in surviving:
            seed = self._reconstruct_seed(i)
            mask = expand_mask(seed, d).astype(np.uint64)
            total = (total + _P_I - mask) % _P_I
        # cancel residual pairwise masks between survivors and dropped
        # clients: reconstruct each dropped j's mask secret key, re-derive
        # the symmetric ECDH pairwise seeds, and invert what each survivor
        # added.
        for j in self._dropped:
            sk_j = self._reconstruct_mask_key(j)
            for i in surviving:
                s = channels.mask_seed(sk_j, self.round_pks[i])
                m = expand_mask(s, d).astype(np.uint64)
                if i < j:   # survivor i added +m (i<j) -> subtract
                    total = (total + _P_I - m) % _P_I
                else:       # survivor i added -m (i>j) -> add back
                    total = (total + m) % _P_I
        if self._wire_plan is not None:
            # exact masked-sum decode: the unmasked total IS the integer
            # sum of the survivors' packed vectors (overflow bound in
            # core/wire/field_quant), so lane extraction + the K*offset
            # correction is bit-identical to summing unmasked quantized
            # vectors directly — the acceptance property test_wire pins
            vec = lane_dequantize_sum(
                np.asarray(total, np.uint64).astype(np.uint32),
                len(surviving), self._round_scale, self._wire_plan,
                d_model)
            # auto-scale: track the observed per-client aggregate
            # magnitude with 2x margin (clip error lands in each client's
            # EF residual, so a transiently tight scale self-corrects)
            per_client = float(np.abs(vec).max()) / max(len(surviving), 1)
            new_scale = suggest_scale(max(2.0 * per_client, 1e-8),
                                      self._wire_plan)
            self._wire_scale = 0.5 * self._wire_scale + 0.5 * new_scale
        else:
            vec = np.asarray(dequantize(total.astype(np.uint32)))
        wsum = sum(self.weights[i] for i in surviving)
        agg_delta_vec = vec / max(wsum, 1e-12)
        agg_delta = vector_to_tree_like(agg_delta_vec.astype(np.float32),
                                        self.global_params)
        self.global_params = jax.tree_util.tree_map(
            lambda g, u: np.asarray(g) + np.asarray(u), self.global_params,
            agg_delta)
        rec = {"round": self.round_idx}
        if self.eval_fn is not None:
            rec.update(self.eval_fn(self.global_params))
            logger.info("secagg round %d: %s", self.round_idx, rec)
        self.history.append(rec)
        with self._lock:
            self.round_idx += 1
            done = self.round_idx >= self.round_num
            if done:
                self._phase = "done"
        if done:
            for rank in range(1, self.n_clients + 1):
                self.send_message(Message(SAMessage.S2C_FINISH, 0, rank))
            last = next((r for r in reversed(self.history)
                         if "test_acc" in r), {})
            self.result = {"params": self.global_params,
                           "history": self.history,
                           "final_test_acc": last.get("test_acc"),
                           "rounds": self.round_num}
            self.finish()
            return
        self._start_round()


def run_secagg_inproc(args, fed, bundle, spec=None,
                      client_factory=None) -> Dict[str, Any]:
    """Server + N SecAgg clients as threads over the in-proc broker.

    ``client_factory(rank, args, trainer) -> SecAggClientManager`` lets tests
    inject faulty clients (dropout / fault injection)."""
    import threading as _threading
    from ...core.distributed.communication.inproc import InProcBroker
    from ..horizontal.runner import _build_spec, _make_eval_fn
    from ..client.trainer import SiloTrainer
    from ...optimizers.registry import create_optimizer

    broker = InProcBroker()
    args.inproc_broker = broker
    spec = _build_spec(fed, bundle, spec)
    n = int(getattr(args, "client_num_per_round", 2))
    rng = jax.random.PRNGKey(int(getattr(args, "random_seed", 0)))
    init_rng, _ = jax.random.split(rng)
    global_params = bundle.init(init_rng, fed.train.x[0, 0])
    server = SecAggServerManager(args, global_params,
                                 eval_fn=_make_eval_fn(spec, fed),
                                 rank=0, size=n + 1, backend="INPROC")
    clients = []
    for r in range(1, n + 1):
        optimizer = create_optimizer(args, spec)
        trainer = SiloTrainer(args, fed, bundle, spec, optimizer)
        if client_factory is not None:
            clients.append(client_factory(r, args, trainer))
        else:
            clients.append(SecAggClientManager(args, trainer, rank=r,
                                               size=n + 1, backend="INPROC"))
    threads = [_threading.Thread(target=c.run, daemon=True) for c in clients]
    for t in threads:
        t.start()
    server.run()
    for t in threads:
        t.join(timeout=30.0)
    return server.result
