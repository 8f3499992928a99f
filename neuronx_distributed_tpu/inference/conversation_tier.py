"""Persistent conversation tier: crash-safe park/resume of idle sessions.

The capacity ladder so far stops at host RAM (``HostPageTier``): at the
millions-of-concurrent-conversations scale every idle session either pins
pages forever or is evicted and pays full re-prefill on the next user turn.
This module adds the third rung — a :class:`ConversationParkStore` on the
checkpoint storage backends (filesystem or object store; the same
``create_checkpoint_storage`` factory, ``_retry`` hardening, and
``read_bytes`` the checkpoint core uses) that holds a parked conversation's
KV pages *plus* its per-request engine state, durable across process death.

Framing and durability discipline are both reused, not reinvented:

* **Page framing** is the ``KVHandoff`` / ``HostPageTier`` shape — one
  ``{cache-leaf path: (L, page_size, kv, hd) array}`` dict per page, a
  per-page crc32 over the sorted leaves (``HostPageTier._crc``), plus
  ``tp_degree`` and ``page_dtype`` stamps so a store written by a foreign
  mesh degree or pool dtype is rejected STRUCTURALLY (degrade to
  re-prefill, never rescale/re-quantize KV mid-stream).
* **Durability** is the checkpoint-integrity pattern: every shard (state
  JSON + page files) is written first, then a ``manifest.json`` carrying
  each shard's sha256 + byte count, and only then the ``done`` marker —
  each write atomic (tmp + rename on the filesystem backend, single-object
  put on the object store). A reader requires the done marker before it
  trusts anything, so a torn write — process killed mid-park — is
  INVISIBLE: the partial directory is quarantined and the conversation
  degrades to re-prefill from the engine's own records.

Failure semantics (the ``park`` seam of ``inference/faults.py`` injects
every one of these deterministically):

* KV shard write fails after retries → the park degrades to a STATE-ONLY
  manifest (prompt + generated tokens + rng base still land durably); the
  next resume re-prefills. The conversation is still evicted — a write
  fault costs latency on resume, never residency.
* Torn manifest (crash before the done marker) → quarantined on the next
  load or :meth:`sweep`; the engine re-prefills from its host-side record
  (in-process) or its snapshot (restart).
* Read failure / bytes corrupted at rest → the sha256 / crc32 mismatch is
  caught, the manifest is quarantined, and resume degrades to re-prefill
  from the parked state (which is verified independently of the pages).

Every degradation lands on the engine's replay path, which the per-request
rng contract (token t of request r draws ``fold_in(fold_in(base, r), t)``)
keeps bit-identical to a cold stream — a park fault is a latency event,
never a wrong token.

The store is FLEET-GLOBAL: every replica of a router fleet shares one
directory, so a conversation parked by a replica that is later drained,
scaled down, or crashed resumes on any survivor (or a freshly restarted
process) by request id alone.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ..checkpoint.storage import BaseCheckpointStorage, create_checkpoint_storage
from .paged_cache import HostPageTier

MANIFEST_VERSION = 1
_DONE = "done"
_QUARANTINED = "quarantined"
_MANIFEST = "manifest.json"
_STATE = "state.json"


class ParkError(RuntimeError):
    """Base class: a park-store operation could not complete."""


class ParkWriteFailed(ParkError):
    """The KV shard write failed (after retries / injected) — the caller
    should fall back to a state-only park."""


class ParkReadFailed(ParkError):
    """A resume read failed (after retries / injected) — degrade to
    re-prefill from the parked state or the engine's own records."""


class ParkIntegrityError(ParkError):
    """Stored bytes failed sha256/crc verification, or the manifest is
    torn/quarantined — the conversation is unresumable from the store and
    must re-prefill."""


def _page_crc(payload: Dict[str, np.ndarray]) -> int:
    return HostPageTier._crc(payload)


def _encode_page(payload: Dict[str, np.ndarray]) -> bytes:
    """Serialize one page's leaf dict to a deterministic byte string:
    sorted leaves, each framed as (key, dtype, shape, raw bytes). No
    pickle — the bytes are content-addressed by the manifest sha256, so
    the encoding must be a pure function of the arrays."""
    out = [b"NXDPAGE1"]
    out.append(len(payload).to_bytes(4, "little"))
    for key in sorted(payload):
        arr = np.ascontiguousarray(payload[key])
        kb = key.encode()
        db = str(arr.dtype).encode()
        out.append(len(kb).to_bytes(4, "little"))
        out.append(kb)
        out.append(len(db).to_bytes(2, "little"))
        out.append(db)
        out.append(len(arr.shape).to_bytes(1, "little"))
        for d in arr.shape:
            out.append(int(d).to_bytes(8, "little"))
        raw = arr.tobytes()
        out.append(len(raw).to_bytes(8, "little"))
        out.append(raw)
    return b"".join(out)


def _decode_page(data: bytes) -> Dict[str, np.ndarray]:
    if data[:8] != b"NXDPAGE1":
        raise ParkIntegrityError("bad page shard magic")
    off = 8
    n = int.from_bytes(data[off:off + 4], "little"); off += 4
    payload: Dict[str, np.ndarray] = {}
    for _ in range(n):
        klen = int.from_bytes(data[off:off + 4], "little"); off += 4
        key = data[off:off + klen].decode(); off += klen
        dlen = int.from_bytes(data[off:off + 2], "little"); off += 2
        dtype = np.dtype(data[off:off + dlen].decode()); off += dlen
        ndim = data[off]; off += 1
        shape = []
        for _ in range(ndim):
            shape.append(int.from_bytes(data[off:off + 8], "little")); off += 8
        blen = int.from_bytes(data[off:off + 8], "little"); off += 8
        raw = data[off:off + blen]; off += blen
        payload[key] = np.frombuffer(raw, dtype=dtype).reshape(shape).copy()
    if off != len(data):
        raise ParkIntegrityError("trailing bytes in page shard")
    return payload


@dataclasses.dataclass
class ParkedConversation:
    """One conversation loaded back from the store. ``payloads`` is None
    for a state-only park (the KV write failed at park time) — the caller
    must re-prefill from ``state``."""

    request_id: int
    manifest_id: str
    state: dict
    payloads: Optional[List[Dict[str, np.ndarray]]]
    tp_degree: int
    page_dtype: str


class ConversationParkStore:
    """Durable park/resume store for idle conversations.

    ``write_fault_hook`` / ``read_fault_hook`` are the ``park`` seam of
    :class:`~neuronx_distributed_tpu.inference.faults.FaultInjector`
    (``on_park_write`` / ``on_park_read``): consulted ONCE per park and
    once per load, they may force a write failure (state-only park), a
    torn manifest (done marker suppressed), a read failure, or an at-rest
    byte flip (which the checksums then catch) — all deterministic, all
    ending in re-prefill."""

    def __init__(self, dirname: str,
                 storage: Optional[BaseCheckpointStorage] = None):
        self.dirname = dirname
        self.storage = storage or create_checkpoint_storage(dirname)
        self.write_fault_hook: Optional[Callable[[], Optional[str]]] = None
        self.read_fault_hook: Optional[Callable[[], Optional[str]]] = None
        self.stats = {"parks": 0, "state_only_parks": 0, "torn_parks": 0,
                      "loads": 0, "load_faults": 0, "quarantined": 0,
                      "removed": 0}

    # --- naming ----------------------------------------------------------

    @staticmethod
    def _conv_dir(rid: int) -> str:
        return f"conv-{int(rid):08d}"

    @staticmethod
    def _rid_of(dirname: str) -> Optional[int]:
        if not dirname.startswith("conv-"):
            return None
        try:
            return int(dirname[len("conv-"):])
        except ValueError:
            return None

    # --- write path -------------------------------------------------------

    def park(self, rid: int, state: dict,
             payloads: Optional[List[Dict[str, np.ndarray]]],
             tp_degree: int = 1, page_dtype: str = "float32") -> Tuple[str, Optional[str]]:
        """Write one conversation durably; returns ``(manifest_id,
        verdict)`` where verdict is the injected fault (None clean,
        ``'fail'`` → the park landed state-only, ``'torn'`` → the shards
        landed but the done marker did not: readers will quarantine it).

        Write order is the checkpoint-integrity discipline: shards →
        manifest (sha256-per-shard) → done marker, each write atomic, so a
        crash at ANY point leaves either a fully-readable park or a torn
        directory that no reader ever trusts."""
        conv = self._conv_dir(rid)
        verdict = self.write_fault_hook() if self.write_fault_hook else None
        # re-park of the same rid: drop the old generation first so a crash
        # mid-rewrite can never pair the old done marker with new shards
        # (the per-shard sha256 would catch the mix anyway; this keeps the
        # window empty rather than merely detected)
        self.storage.remove_dir(conv)
        self.storage.makedirs(conv)

        if verdict == "fail":
            payloads = None  # the KV shard write "failed" — park state-only
            self.stats["state_only_parks"] += 1

        files: Dict[str, dict] = {}
        crcs: List[int] = []
        state_bytes = json.dumps(state, sort_keys=True).encode()
        self.storage.save_bytes(state_bytes, f"{conv}/{_STATE}")
        files[_STATE] = {"sha256": hashlib.sha256(state_bytes).hexdigest(),
                         "bytes": len(state_bytes)}
        for i, payload in enumerate(payloads or []):
            data = _encode_page(payload)
            rel = f"page-{i:06d}.bin"
            self.storage.save_bytes(data, f"{conv}/{rel}")
            files[rel] = {"sha256": hashlib.sha256(data).hexdigest(),
                          "bytes": len(data)}
            crcs.append(_page_crc(payload))

        manifest = {
            "version": MANIFEST_VERSION,
            "algo": "sha256",
            "request_id": int(rid),
            "pages": len(crcs),
            "crcs": crcs,
            "tp_degree": int(tp_degree),
            "page_dtype": str(page_dtype),
            "state_only": payloads is None,
            "files": files,
        }
        self.storage.save_text(json.dumps(manifest, sort_keys=True),
                               f"{conv}/{_MANIFEST}")
        if verdict == "torn":
            # the crash-mid-park shape: everything but the done marker
            # landed. Readers never trust it; sweep() quarantines it.
            self.stats["torn_parks"] += 1
            return conv, verdict
        self.storage.save_text(_DONE, f"{conv}/{_DONE}")
        self.stats["parks"] += 1
        return conv, verdict

    # --- read path --------------------------------------------------------

    def contains(self, rid: int) -> bool:
        """True iff a COMPLETE (done-marked, unquarantined) park exists."""
        conv = self._conv_dir(rid)
        return (self.storage.file_exists(f"{conv}/{_DONE}")
                and not self.storage.file_exists(f"{conv}/{_QUARANTINED}"))

    def manifest(self, rid: int) -> dict:
        conv = self._conv_dir(rid)
        return json.loads(self.storage.load_text(f"{conv}/{_MANIFEST}"))

    def parked_bytes(self, rid: int) -> int:
        """Total durable bytes of one parked conversation (manifest sum):
        what an idle conversation holds lives on disk, not in device/host
        memory."""
        m = self.manifest(rid)
        return sum(int(f["bytes"]) for f in m["files"].values())

    def load(self, rid: int) -> ParkedConversation:
        """Read one parked conversation back, verifying every shard's
        sha256 and every page's crc32 against the manifest. Torn or
        corrupt state quarantines the directory and raises — the caller
        degrades to re-prefill. A state-only park returns
        ``payloads=None`` (valid state, no KV)."""
        conv = self._conv_dir(rid)
        self.stats["loads"] += 1
        if self.storage.file_exists(f"{conv}/{_QUARANTINED}"):
            raise ParkIntegrityError(f"{conv} is quarantined")
        if not self.storage.file_exists(f"{conv}/{_DONE}"):
            # torn write: the park never completed. Quarantine so no later
            # reader half-trusts it, then degrade.
            if self.storage.file_exists(f"{conv}/{_MANIFEST}") or \
                    self.storage.file_exists(f"{conv}/{_STATE}"):
                self.quarantine(rid)
            raise ParkIntegrityError(f"{conv} has no done marker (torn park)")

        verdict = self.read_fault_hook() if self.read_fault_hook else None
        if verdict == "fail":
            self.stats["load_faults"] += 1
            raise ParkReadFailed(f"injected read failure for {conv}")

        try:
            m = json.loads(self.storage.load_text(f"{conv}/{_MANIFEST}"))
        except Exception as e:
            self.quarantine(rid)
            raise ParkIntegrityError(f"{conv} manifest unreadable: {e}")
        if m.get("version") != MANIFEST_VERSION or m.get("algo") != "sha256":
            self.quarantine(rid)
            raise ParkIntegrityError(f"{conv} manifest version/algo mismatch")

        shards: Dict[str, bytes] = {}
        try:
            for rel in sorted(m["files"]):
                shards[rel] = self.storage.read_bytes(f"{conv}/{rel}")
        except Exception as e:
            self.stats["load_faults"] += 1
            raise ParkReadFailed(f"{conv} shard read failed: {e}")

        if verdict == "corrupt":
            # garble one byte of the largest shard (a page when present,
            # else the state) — the flip is REAL, so verification failing
            # below proves the checksum caught actual at-rest damage
            victim = max(sorted(shards), key=lambda r: len(shards[r]))
            raw = bytearray(shards[victim])
            raw[len(raw) // 2] ^= 0xFF
            shards[victim] = bytes(raw)

        for rel, want in m["files"].items():
            data = shards.get(rel)
            if (data is None or len(data) != int(want["bytes"])
                    or hashlib.sha256(data).hexdigest() != want["sha256"]):
                self.quarantine(rid)
                raise ParkIntegrityError(f"{conv}/{rel} failed sha256 verify")

        state = json.loads(shards[_STATE].decode())
        payloads: Optional[List[Dict[str, np.ndarray]]] = None
        if not m.get("state_only"):
            payloads = []
            for i in range(int(m["pages"])):
                payload = _decode_page(shards[f"page-{i:06d}.bin"])
                if _page_crc(payload) != int(m["crcs"][i]):
                    self.quarantine(rid)
                    raise ParkIntegrityError(
                        f"{conv} page {i} failed crc32 verify")
                payloads.append(payload)
        return ParkedConversation(
            request_id=int(m["request_id"]), manifest_id=conv, state=state,
            payloads=payloads, tp_degree=int(m.get("tp_degree", 1)),
            page_dtype=str(m.get("page_dtype", "float32")))

    def recover_state(self, rid: int) -> Optional[dict]:
        """Best-effort STATE recovery from a damaged park — the degradation
        ladder's middle rung: when the full load failed (torn done marker,
        corrupt page shard, read fault) the state JSON may still be intact,
        and a verified state is enough to re-prefill the stream
        bit-identically. Strictly verify-first: the state is returned ONLY
        when the manifest is readable and the state shard passes its sha256
        — a parseable-but-unverified state could replay wrong tokens, which
        the oracle forbids. Never raises; None means the caller must fall
        back to its own records (in-memory park entry or snapshot) or
        reject the resume as unresumable."""
        conv = self._conv_dir(rid)
        try:
            m = json.loads(self.storage.load_text(f"{conv}/{_MANIFEST}"))
            want = m["files"][_STATE]
            data = self.storage.read_bytes(f"{conv}/{_STATE}")
            if (len(data) != int(want["bytes"])
                    or hashlib.sha256(data).hexdigest() != want["sha256"]):
                return None
            return json.loads(data.decode())
        except Exception:
            return None

    # --- lifecycle --------------------------------------------------------

    def quarantine(self, rid: int) -> None:
        """Mark a conversation directory poison: it stops appearing in
        :meth:`list_parked`/:meth:`contains` and every later load refuses
        it. The bytes are kept for post-mortem — quarantine is a marker,
        not a delete, so the operation is atomic on every backend."""
        conv = self._conv_dir(rid)
        self.storage.save_text(_QUARANTINED, f"{conv}/{_QUARANTINED}")
        self.stats["quarantined"] += 1

    def remove(self, rid: int) -> None:
        """Drop a conversation after a successful resume (or abandonment)."""
        self.storage.remove_dir(self._conv_dir(rid))
        self.stats["removed"] += 1

    def list_parked(self) -> List[int]:
        """Request ids with COMPLETE parks, ascending — the restart
        recovery surface: a fresh process enumerates these and accepts
        ``submit(resume=rid)`` for each."""
        out = []
        for d in self.storage.list_dirs():
            rid = self._rid_of(d)
            if rid is not None and self.contains(rid):
                out.append(rid)
        return sorted(out)

    def sweep(self) -> Tuple[List[int], List[int]]:
        """Crash cleanup, run once at store attach: quarantine every torn
        directory (no done marker — the process died mid-park). Returns
        ``(resumable rids, newly quarantined rids)``."""
        ok, torn = [], []
        for d in self.storage.list_dirs():
            rid = self._rid_of(d)
            if rid is None:
                continue
            if self.contains(rid):
                ok.append(rid)
            elif not self.storage.file_exists(f"{d}/{_QUARANTINED}"):
                self.quarantine(rid)
                torn.append(rid)
        return sorted(ok), sorted(torn)
