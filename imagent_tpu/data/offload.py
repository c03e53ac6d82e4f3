"""Decode offload: ship JPEG decode to non-training CPU hosts.

A chip that trains r18@448 an order of magnitude faster than the
reference's GPUs makes host JPEG decode the wall (ROADMAP item 5; the
per-chip rate itself is not measured on the current code): a TPU host
has a fixed CPU budget, and past it
the chips starve however many ``--workers`` are configured. This module
moves the decode OFF the training hosts: any number of plain CPU boxes
run ``python -m imagent_tpu.data.serve`` against the same dataset
(their own mount/copy — **shared-nothing**, no coordination between
decode hosts or with the trainer beyond the request itself), and the
training hosts' loaders ship batch row-lists out and receive ready
uint8 batches back into the existing staging queue.

Why this is safe to bolt onto the deterministic stream: a batch's
pixels are a pure function of ``(dataset, image_size, seed, epoch,
row)`` — the augmentation stream is seeded per ``(seed, epoch, row)``
(``data/imagefolder.py::_aug_seeds``) and the sample order per
``data/stream.py`` — so a decode host with the same dataset and config
produces byte-identical batches to a local decode. The hello handshake
pins exactly that key (and every response's labels are verified
against the trainer's own label table — a wrong ``--data-root`` on a
decode host is caught on the first batch, not after an epoch of
silently-wrong pixels).

Failure discipline (the PR 1 resilience kit): every request runs under
``retry_call`` with jittered backoff; an endpoint that fails its
budget is marked down with exponential backoff (capped) and the batch
falls back to LOCAL decode — a dead decode service costs throughput
and a counted ``offload_fallbacks``/warning, never the run. Down
endpoints keep being re-probed, so a restarted service re-attaches
mid-epoch.

Wire format: 4-byte big-endian length + JSON header, then raw
payloads — images as the canonical uint8 NHWC batch (1 byte/pixel, the
same wire discipline as the H2D path) and labels as int32. This module
is **jax-free** including its import chain (asserted by
tests/test_stream.py): it runs on decode hosts with no accelerator
stack at all.
"""

from __future__ import annotations

import json
import os
import socket
import socketserver
import struct
import threading
import time

import numpy as np

from imagent_tpu.resilience.retry import retry_call
from imagent_tpu.telemetry import trace as trace_mod

PROTOCOL_VERSION = 1

# Client-side budgets: small — a slow/ dead endpoint must cost one
# batch's patience, after which local decode carries the epoch while
# the endpoint backs off.
_REQUEST_ATTEMPTS = 2
_CONNECT_TIMEOUT_S = 5.0
_IO_TIMEOUT_S = 60.0
_DOWN_BACKOFF_BASE_S = 2.0
_DOWN_BACKOFF_CAP_S = 30.0


class OffloadConfigError(OSError):
    """A config-class refusal (fingerprint mismatch, label
    disagreement): retrying can never heal it — the endpoint is
    disabled for the rest of the run instead of re-probed."""


def parse_endpoints(spec: str) -> list[tuple[str, int]]:
    """``"host:port[,host:port...]"`` → [(host, port)]; loud on typos
    (a malformed endpoint list must fail the run at config time, not
    silently decode everything locally)."""
    out: list[tuple[str, int]] = []
    for part in filter(None, (p.strip() for p in spec.split(","))):
        host, sep, port = part.rpartition(":")
        if not sep or not host or not port.isdigit():
            raise ValueError(
                f"--decode-offload endpoint {part!r} is not host:port")
        out.append((host, int(port)))
    if not out:
        raise ValueError(f"--decode-offload {spec!r} names no endpoints")
    return out


# ---------------------------------------------------------------------------
# Framing
# ---------------------------------------------------------------------------


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = bytearray(n)
    view = memoryview(buf)
    got = 0
    while got < n:
        k = sock.recv_into(view[got:], n - got)
        if k == 0:
            raise ConnectionError("peer closed mid-message")
        got += k
    return bytes(buf)


def send_msg(sock: socket.socket, header: dict, *payloads: bytes) -> None:
    data = json.dumps(header).encode()
    sock.sendall(struct.pack(">I", len(data)) + data)
    for p in payloads:
        if len(p):
            sock.sendall(p)


def recv_msg(sock: socket.socket) -> dict:
    (n,) = struct.unpack(">I", _recv_exact(sock, 4))
    if n > (1 << 20):
        raise ValueError(f"offload header implausibly large ({n} bytes)")
    return json.loads(_recv_exact(sock, n))


# ---------------------------------------------------------------------------
# Client (runs inside the training hosts' loaders)
# ---------------------------------------------------------------------------


class _Endpoint:
    __slots__ = ("host", "port", "sock", "fails", "down_until")

    def __init__(self, host: str, port: int):
        self.host = host
        self.port = port
        self.sock: socket.socket | None = None
        self.fails = 0
        self.down_until = 0.0

    @property
    def name(self) -> str:
        return f"{self.host}:{self.port}"


class OffloadClient:
    """One loader's connection pool to the decode service endpoints.

    ``decode(rows, epoch)`` returns ``(images, quarantined)`` with
    ``images=None`` when every endpoint is down/unreachable — the
    caller decodes locally and counts the fallback. Batches round-robin
    across healthy endpoints (N decode hosts ≈ N× the decode budget;
    each batch still lands on exactly one host, keeping the service
    shared-nothing)."""

    def __init__(self, endpoints: str, fingerprint: dict):
        self._eps = [_Endpoint(h, p)
                     for h, p in parse_endpoints(endpoints)]
        self._fingerprint = dict(fingerprint)
        self._rr = 0
        self._warned: set[str] = set()

    # -- connection management -------------------------------------------

    def _connect(self, ep: _Endpoint) -> socket.socket:
        sock = socket.create_connection((ep.host, ep.port),
                                        timeout=_CONNECT_TIMEOUT_S)
        sock.settimeout(_IO_TIMEOUT_S)
        send_msg(sock, {"v": PROTOCOL_VERSION, "op": "hello",
                        "fingerprint": self._fingerprint})
        resp = recv_msg(sock)
        if not resp.get("ok"):
            # A fingerprint refusal is a CONFIG error (wrong dataset /
            # seed / image size / decode path on the decode host) —
            # backing off and retrying would never fix it; the
            # endpoint is disabled for the run and decode proceeds
            # locally.
            sock.close()
            raise OffloadConfigError(
                f"offload {ep.name} refused handshake: "
                f"{resp.get('error', 'unknown')}")
        return sock

    def _drop(self, ep: _Endpoint) -> None:
        if ep.sock is not None:
            try:
                ep.sock.close()
            except OSError:
                pass
            ep.sock = None

    def _mark_down(self, ep: _Endpoint, err: Exception) -> None:
        self._drop(ep)
        ep.fails += 1
        if isinstance(err, OffloadConfigError):
            # Misconfigured, not unreachable: re-probing would burn a
            # decode + a wire round-trip per backoff window forever on
            # an error that cannot heal. Disabled for the run.
            ep.down_until = float("inf")
            print(f"WARNING: decode-offload {ep.name} DISABLED for "
                  f"this run ({err}); falling back to local decode — "
                  "fix the decode host's flags/dataset and restart it "
                  "alongside a fresh run", flush=True)
            return
        backoff = min(_DOWN_BACKOFF_CAP_S,
                      _DOWN_BACKOFF_BASE_S * (2.0 ** (ep.fails - 1)))
        ep.down_until = time.time() + backoff
        if ep.name not in self._warned:
            self._warned.add(ep.name)
            print(f"WARNING: decode-offload {ep.name} unavailable "
                  f"({type(err).__name__}: {err}); falling back to "
                  f"local decode, re-probing in {backoff:.0f}s",
                  flush=True)

    # -- the one request -------------------------------------------------

    def _request(self, ep: _Endpoint, rows: np.ndarray,
                 epoch: int) -> tuple[np.ndarray, np.ndarray, int]:
        if ep.sock is None:
            ep.sock = self._connect(ep)
        try:
            send_msg(ep.sock, {"v": PROTOCOL_VERSION, "op": "decode",
                               "epoch": int(epoch),
                               "rows": [int(r) for r in rows]})
            resp = recv_msg(ep.sock)
            if not resp.get("ok"):
                raise OSError(f"offload {ep.name} decode error: "
                              f"{resp.get('error', 'unknown')}")
            shape = tuple(int(x) for x in resp["shape"])
            images = np.frombuffer(
                _recv_exact(ep.sock, int(resp["images_nbytes"])),
                np.uint8).reshape(shape)
            labels = np.frombuffer(
                _recv_exact(ep.sock, int(resp["labels_nbytes"])),
                np.int32)
            return images, labels, int(resp.get("quarantined", 0))
        except (OSError, ValueError, KeyError, struct.error):
            # Any torn exchange poisons the connection's framing:
            # reconnect on the next attempt.
            self._drop(ep)
            raise

    def decode(self, rows: np.ndarray, epoch: int,
               expect_labels: np.ndarray | None = None,
               ) -> tuple[np.ndarray | None, int]:
        """Decode ``rows`` on some healthy endpoint; ``(None, 0)`` when
        none is reachable (caller falls back to local decode).

        ``expect_labels``: the trainer's own label table entries for
        ``rows`` — a mismatch means the decode host scanned a DIFFERENT
        dataset than the fingerprint suggested (same size, different
        content); the endpoint is dropped rather than trusted."""
        now = time.time()
        n = len(self._eps)
        for k in range(n):
            ep = self._eps[(self._rr + k) % n]
            if ep.down_until > now:
                continue
            # Each attempted endpoint is one `data/offload` span
            # (endpoint + retry-state attrs): a degrading offload pool
            # shows up in the merged timeline as lengthening request
            # spans and error-tagged ones — not just an end-of-epoch
            # fallback counter.
            t0_span = time.perf_counter()
            try:
                images, labels, q = retry_call(
                    self._request, ep, rows, epoch,
                    attempts=_REQUEST_ATTEMPTS, base_delay=0.05,
                    describe=f"offload decode via {ep.name}")
                if (expect_labels is not None
                        and not np.array_equal(
                            labels, np.asarray(expect_labels, np.int32))):
                    raise OffloadConfigError(
                        f"offload {ep.name} labels disagree with the "
                        "local dataset scan — its --data-root is not "
                        "this run's dataset")
                ep.fails = 0
                self._rr = (self._rr + k + 1) % n
                trace_mod.complete(
                    "data/offload", t0_span, time.perf_counter(),
                    cat="data", endpoint=ep.name, rows=int(len(rows)),
                    ok=True)
                return images, q
            except (OSError, ValueError, KeyError, struct.error) as e:
                self._mark_down(ep, e)
                trace_mod.complete(
                    "data/offload", t0_span, time.perf_counter(),
                    cat="data", endpoint=ep.name, rows=int(len(rows)),
                    ok=False, error=type(e).__name__,
                    retries=int(ep.fails))
        # Every endpoint down/unreachable: the batch falls back to
        # LOCAL decode — an instant marks the moment on the timeline.
        trace_mod.instant("data/offload_fallback", cat="data",
                          rows=int(len(rows)))
        return None, 0

    def close(self) -> None:
        for ep in self._eps:
            self._drop(ep)


# ---------------------------------------------------------------------------
# Server (runs on the decode hosts; CLI in data/serve.py)
# ---------------------------------------------------------------------------


class _Handler(socketserver.BaseRequestHandler):
    def handle(self):  # one connection = one split's session
        srv: DecodeServer = self.server.decode_server  # type: ignore
        sock = self.request
        sock.settimeout(_IO_TIMEOUT_S * 10)  # idle trainers are fine
        loader = None
        try:
            while True:
                try:
                    msg = recv_msg(sock)
                except (ConnectionError, socket.timeout, OSError):
                    return
                op = msg.get("op")
                if op == "hello":
                    loader, err = srv.match(msg.get("fingerprint") or {})
                    if loader is None:
                        send_msg(sock, {"v": PROTOCOL_VERSION,
                                        "ok": False, "error": err})
                        return
                    send_msg(sock, {"v": PROTOCOL_VERSION, "ok": True})
                elif op == "decode":
                    if loader is None:
                        send_msg(sock, {"v": PROTOCOL_VERSION,
                                        "ok": False,
                                        "error": "decode before hello"})
                        return
                    srv.count_request()
                    rows = np.asarray(msg.get("rows", []), np.int64)
                    try:
                        # Batch-level decode is serialized per split:
                        # the loader's lazy pool init and quarantine
                        # delta are not safe under concurrent handler
                        # threads, and each batch already fans out over
                        # ALL of this host's --workers — concurrent
                        # trainers queue here, they don't starve.
                        with srv.decode_lock(loader):
                            before = loader._quarantined
                            images = loader._decode_rows(
                                rows, int(msg["epoch"]))
                            q = loader._quarantined - before
                        labels = loader.labels[rows].astype(np.int32)
                    except Exception as e:  # report, keep serving
                        send_msg(sock, {"v": PROTOCOL_VERSION,
                                        "ok": False,
                                        "error": f"{type(e).__name__}: "
                                                 f"{e}"})
                        continue
                    images = np.ascontiguousarray(images, np.uint8)
                    labels = np.ascontiguousarray(labels, np.int32)
                    send_msg(sock, {"v": PROTOCOL_VERSION, "ok": True,
                                    "shape": list(images.shape),
                                    "images_nbytes": images.nbytes,
                                    "labels_nbytes": labels.nbytes,
                                    "quarantined": int(q)},
                             images.tobytes(), labels.tobytes())
                else:
                    send_msg(sock, {"v": PROTOCOL_VERSION, "ok": False,
                                    "error": f"unknown op {op!r}"})
        except (ConnectionError, BrokenPipeError, OSError):
            return


class _Server(socketserver.ThreadingTCPServer):
    allow_reuse_address = True
    daemon_threads = True


class DecodeServer:
    """The decode host's half: loaders per split, built lazily on the
    first hello that names the split (the val dir need not exist on a
    host serving only train), requests decoded concurrently (one
    thread per trainer connection; the decode pool / native threads
    are shared and safe under concurrent submission)."""

    def __init__(self, cfg, host: str = "0.0.0.0", port: int = 0,
                 die_after_requests: int = 0):
        if cfg.decode_offload:
            raise ValueError("the decode server must not itself "
                             "offload (decode_offload must be empty "
                             "in the server config)")
        self.cfg = cfg
        self._loaders: dict[str, object] = {}
        self._lock = threading.Lock()
        self._decode_locks: dict[int, threading.Lock] = {}
        self._requests = 0
        # Drill hook (tests/test_offload.py): hard-die after N decode
        # requests — the deterministic mid-epoch service death the
        # client's degrade-to-local path is drilled against.
        self._die_after = int(die_after_requests)
        self._tcp = _Server((host, port), _Handler)
        self._tcp.decode_server = self  # type: ignore[attr-defined]

    @property
    def port(self) -> int:
        return self._tcp.server_address[1]

    def _loader(self, split: str):
        # Built directly (not via make_loaders, which scans BOTH
        # splits): a host serving only train must not require a val
        # dir, and vice versa.
        if self.cfg.dataset == "tar":
            from imagent_tpu.data.tarshards import TarShardLoader as Cls
        else:
            from imagent_tpu.data.imagefolder import (
                ImageFolderLoader as Cls,
            )
        with self._lock:
            if split not in self._loaders:
                self._loaders[split] = Cls(self.cfg, 0, 1,
                                           global_batch=1, split=split)
            return self._loaders[split]

    def match(self, fp: dict) -> tuple[object | None, str]:
        """Resolve a hello fingerprint to a loader, or an error string.
        The comparison is against the loader's OWN fingerprint — one
        source of truth for what must agree for byte-identical
        decode."""
        split = fp.get("split")
        if split not in ("train", "val"):
            return None, f"unknown split {split!r}"
        try:
            loader = self._loader(split)
        except Exception as e:
            return None, f"loader build failed: {type(e).__name__}: {e}"
        mine = loader.fingerprint()
        if fp != mine:
            return None, (f"fingerprint mismatch: trainer {fp} vs "
                          f"decode host {mine}")
        return loader, ""

    def decode_lock(self, loader) -> threading.Lock:
        """One lock per loader instance (i.e. per split)."""
        with self._lock:
            return self._decode_locks.setdefault(id(loader),
                                                 threading.Lock())

    def count_request(self) -> None:
        with self._lock:
            self._requests += 1
            if self._die_after and self._requests > self._die_after:
                print("DRILL: decode server dying after "
                      f"{self._die_after} requests", flush=True)
                os._exit(1)

    def serve_forever(self) -> None:
        self._tcp.serve_forever()

    def serve_background(self) -> threading.Thread:
        t = threading.Thread(target=self._tcp.serve_forever,
                             daemon=True, name="decode-serve")
        t.start()
        return t

    def close(self) -> None:
        self._tcp.shutdown()
        self._tcp.server_close()
        for ld in self._loaders.values():
            close = getattr(ld, "close", None)
            if close is not None:
                close()
