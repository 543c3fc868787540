"""``python -m tpuic.serve`` — online inference driver.

Three request sources, all feeding the same InferenceEngine:

- **stdin JSONL** (default): one request per line,
  ``{"id": "r1", "path": "img.png"}`` (``id`` optional, defaults to the
  path).  Responses stream to --out (default stdout) as JSONL:
  ``{"id", "pred", "prob", "topk": [[name, prob], ...]}``.
- **directory watch** (``--watch DIR``): polls DIR for new image files
  and classifies each once; ``--once`` processes the current contents
  and exits (the tier-1-testable mode).
- **socket JSONL** (``--listen HOST:PORT``): the replica transport the
  router (``python -m tpuic.serve.router``, docs/serving.md "Replica
  routing and failover") drives.  Same request lines as stdin plus a
  ``{"b64", "shape", "dtype"}`` raw-array payload (tpuic/serve/wire.py)
  and a ``{"op": "ping"}`` liveness probe answered with queue depth;
  responses go back on the requesting connection, keyed by id.
  ``--ready-file`` atomically publishes the bound port + pid once the
  engine is warmed — the router's port-handoff channel.

Decode (PIL) of request N+1 overlaps the device call for batch N: the
driver only *submits* work and drains completed futures opportunistically
— the engine's batcher thread owns the device.

    python -m tpuic.serve --ckpt-dir dtmodel/cp --model auto < reqs.jsonl
    python -m tpuic.serve --ckpt-dir dtmodel/cp --watch incoming/ --once

A final stats line (queue wait, pad efficiency, bucket histogram,
latency percentiles, compile counts) goes to stderr on shutdown.

Graceful shutdown (docs/robustness.md): SIGTERM/SIGINT latch a
PreemptionGuard (the trainer's mechanism, runtime/preemption.py) instead
of killing the process mid-batch — the driver stops accepting requests,
drains everything in flight for up to ``--drain-timeout`` seconds
(stragglers get a per-request error line, never a silent drop), closes
the engine, and exits 0. A scheduler eviction loses zero accepted
requests that the device can finish inside the grace window.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from collections import deque
from concurrent.futures import Future as _FutFuture
from concurrent.futures import TimeoutError as _FutTimeout

import numpy as np

from tpuic.runtime import faults as _faults  # stdlib-only import
from tpuic.serve import wire  # stdlib-only import
from tpuic.serve.admission import AdmissionError  # stdlib-only import


def _load_image(path: str, size: int) -> np.ndarray:
    """Decode + resize EXACTLY like the training/predict pipeline
    (folder.py -> transforms.resize_nearest): the checkpoint's val
    accuracy was measured on nearest-resized pixels, and serving the
    same image through a different interpolation would silently shift
    predictions relative to `python -m tpuic.predict`."""
    from PIL import Image

    from tpuic.data.transforms import resize_nearest
    img = np.asarray(Image.open(path).convert("RGB"), np.uint8)
    return resize_nearest(img, size)


def _class_names(ckpt_dir: str, model: str, num_classes: int,
                 classes_file: str) -> dict:
    """index -> display name: --classes file (one name per line) wins,
    else the class_to_idx.json sidecar the Trainer writes, else indices."""
    names = {i: str(i) for i in range(num_classes)}
    if classes_file:
        with open(classes_file) as f:
            for i, line in enumerate(ln.strip() for ln in f):
                if line:
                    names[i] = line
        return names
    sidecar = os.path.join(ckpt_dir, model, "class_to_idx.json")
    try:
        with open(sidecar) as f:
            names.update({int(v): k for k, v in json.load(f).items()})
    except (OSError, ValueError):
        pass
    return names


def _result_record(rid, probs, order, names, k: int) -> dict:
    """One response record: ``{"id", "pred", "prob", "topk"}`` — the
    shape every transport (stdin, watch, socket) emits."""
    topk = [[names.get(int(order[0, j]), str(int(order[0, j]))),
             round(float(probs[0, order[0, j]]), 6)]
            for j in range(k)]
    return {"id": rid, "pred": topk[0][0], "prob": topk[0][1],
            "topk": topk}


def serve_socket(engine, *, listen: str, names, top_k: int, size: int,
                 guard, beat, drain_timeout: float = 30.0,
                 ready_file: str = "", prom_port=None,
                 log=lambda msg: print(msg, file=sys.stderr)) -> int:
    """The socket-JSONL replica transport (docs/serving.md, "Replica
    routing and failover").

    Accepts connections on ``listen`` (HOST:PORT, port 0 = kernel
    assigned) and speaks newline-delimited JSON per connection:

    - request lines as in stdin mode (``path`` or a ``b64`` raw-array
      payload, optional SLA fields honored under --admission), answered
      on the SAME connection with the usual result record or a typed
      error line (wire.py — identical shape to the stdin tier's);
      responses are keyed by id and may arrive out of submission order
      (a deadline shed resolves before its batchmates).
    - ``{"op": "ping", "id": ...}`` -> ``{"op": "pong", "id",
      "queue_depth", "inflight", "pid"}`` — the router's live probe.

    Single-threaded select loop (the stdin design, multiplexed): reads
    submit, completed futures flush opportunistically each tick, and
    the SIGTERM latch drains everything in flight for up to
    ``drain_timeout`` seconds with typed straggler lines — the PR-2
    preemption contract, per connection.

    ``ready_file`` is written (atomic, wire.py) once the socket is
    bound — and the engine is already warmed by then — so the router's
    spawn handshake never races warmup.

    Fault points (runtime/faults.py): ``replica_crash`` SIGKILLs this
    process at the Nth accepted request; ``replica_wedge`` stops
    servicing the socket there (pings included) so the heartbeat goes
    stale — the two replica-death shapes the router must survive.
    """
    import select
    import signal as _signal
    import socket as _socket

    host, port = wire.parse_hostport(listen)
    srv = _socket.create_server((host, port), backlog=64)
    srv.setblocking(False)
    bound = srv.getsockname()[1]
    if ready_file:
        # Model identity rides the handoff (docs/serving.md, "Model
        # lifecycle"): digest + dtype-ladder tags let the router refuse
        # a silently-heterogeneous fleet before routing one request.
        # The ready file records the BOOT identity; the live identity
        # (post-swap) is whatever the pong says.
        wire.write_ready_file(ready_file, port=int(bound), pid=os.getpid(),
                              prom_port=prom_port,
                              digest=engine.model_digest,
                              dtypes=list(engine.variant_tags()),
                              generation=engine.generation)
    log(f"[serve] socket-JSONL transport on {host}:{bound}"
        + (f" (ready file {ready_file})" if ready_file else ""))

    # socket -> {"buf": bytes, "out": bytearray, "out_ofs": int,
    #            "pending": deque}; "out" holds unsent response bytes
    # from index "out_ofs" on (cleared when fully drained, so its
    # truthiness means "has pending output" at every check site).
    conns: dict = {}
    served = 0
    accepted = 0  # request counter: the fault points' step axis
    # A peer that stops reading grows its out buffer without bound;
    # past this the connection is condemned (the router's failover
    # handles its in-flight) rather than ballooning the replica.
    max_out_buf = 8 << 20

    def close_conn(sock) -> None:
        st = conns.pop(sock, None)
        try:
            sock.close()
        except OSError:
            pass
        if st is None:
            return
        for _, fut in st["pending"]:
            # Client gone: nothing to deliver to. Swallow the outcome
            # so an abandoned future never logs "exception never
            # retrieved" noise.
            fut.add_done_callback(lambda f: f.cancelled() or f.exception())

    def pump_out(sock) -> None:
        """Drain as much of the connection's out buffer as the kernel
        will take WITHOUT blocking.  A stalled peer must never stall
        the select loop: one slow sendall here used to freeze pings to
        every OTHER connection (and the supervisor heartbeat) for up
        to its 5s timeout — longer than the router's 3s ping window —
        so healthy links accrued breaker failures for this peer's
        sins.

        The buffer is a bytearray consumed via an offset (compacted
        every 256KB) so a slow drain costs one memmove per compaction,
        not a full copy of the multi-MB remainder per partial send."""
        st = conns.get(sock)
        if st is None or not st["out"]:
            return
        try:
            n = sock.send(memoryview(st["out"])[st["out_ofs"]:])
        except (BlockingIOError, InterruptedError):
            return  # kernel buffer full: the writable set drains it
        except OSError:
            close_conn(sock)
            return
        st["out_ofs"] += n
        if st["out_ofs"] >= len(st["out"]):
            del st["out"][:]
            st["out_ofs"] = 0
        elif st["out_ofs"] > (1 << 18):
            del st["out"][:st["out_ofs"]]
            st["out_ofs"] = 0

    def send(sock, rec: dict) -> None:
        st = conns.get(sock)
        if st is None:
            return
        st["out"] += (json.dumps(rec) + "\n").encode()
        if len(st["out"]) - st["out_ofs"] > max_out_buf:
            close_conn(sock)  # peer stopped reading: conclusive
            return
        pump_out(sock)

    def handle_line(sock, st, raw: str) -> None:
        nonlocal accepted
        try:
            req = json.loads(raw)
            if not isinstance(req, dict):
                raise ValueError("not an object")
        except ValueError:
            send(sock, wire.error_record(
                None, f"bad request line: {raw[:80]}"))
            return
        if req.get("op") == "ping":
            send(sock, {"id": req.get("id"), "op": "pong",
                        "queue_depth": engine.queue_depth(),
                        "inflight": sum(len(s["pending"])
                                        for s in conns.values()),
                        # Model identity (docs/serving.md, "Model
                        # lifecycle"): the router's heterogeneous-fleet
                        # gate and the rollout driver's promotion check
                        # both read the LIVE digest from pongs — a
                        # hot-swap shows up within one ping interval.
                        "digest": engine.model_digest,
                        "generation": engine.generation,
                        "pid": os.getpid()})
            return
        if req.get("op") == "swap":
            # Control line, not traffic: gates + flips on a worker
            # thread (submit_swap) so pings keep flowing; the result
            # record (or typed swap_corrupt/swap_accuracy verdict)
            # rides the normal pending/flush machinery, keyed by id.
            rid = str(req.get("id", "swap"))
            st["pending"].append((rid, submit_swap(engine, req, log)))
            return
        accepted += 1
        if _faults.fire("replica_crash", accepted):
            os.kill(os.getpid(), _signal.SIGKILL)
        if _faults.fire("replica_wedge", accepted):
            w = _faults.param("replica_wedge")
            time.sleep(3600.0 if w is None else float(w))  # tpuic-ok: TPU101 fault param is a host float
        rid = str(req.get("id", req.get("path", accepted)))
        try:
            if req.get("b64") is not None:
                img = wire.decode_array(req)
            elif req.get("path") is not None:
                img = _load_image(str(req["path"]), size)
            else:
                raise ValueError("request needs 'path' or 'b64'")
        except Exception as e:  # noqa: BLE001
            send(sock, wire.error_record(rid, f"decode: {e}"))
            return
        sla = {}
        if engine.admission is not None:
            sla = {f: req[f] for f in ("priority", "deadline_ms", "tenant")
                   if req.get(f) is not None}
            sla.setdefault("timeout", 0)
        if req.get("serve_dtype") is not None:
            # Dtype-ladder rung selection (docs/performance.md,
            # "Quantized serving").  The request key is serve_dtype —
            # NOT "dtype", which the b64 array payload already uses for
            # the ARRAY's element type (wire.py).  An unconfigured rung
            # gets a typed error line via the ValueError arm below.
            sla["dtype"] = str(req["serve_dtype"])
        try:
            st["pending"].append((rid, engine.submit(img, **sla)))
        except (AdmissionError, ValueError, TypeError) as e:
            send(sock, wire.error_record(rid, e))

    def flush(sock, st) -> None:
        """Emit every completed future on this connection (any order —
        responses are keyed by id, and a shed must not wait behind the
        batch ahead of it)."""
        nonlocal served
        still = deque()
        while st["pending"]:
            rid, fut = st["pending"].popleft()
            if not fut.done():
                still.append((rid, fut))
                continue
            if fut.cancelled():
                send(sock, wire.error_record(rid, "cancelled"))
            elif fut.exception() is not None:
                send(sock, wire.error_record(rid, fut.exception()))
            else:
                res = fut.result()
                if isinstance(res, dict):
                    # Control-line outcome (a swap_result): already a
                    # wire record — not counted as served traffic.
                    send(sock, {**res, "id": rid})
                else:
                    probs, order = res
                    send(sock, _result_record(rid, probs, order, names,
                                              top_k))
                    served += 1
            if sock not in conns:
                # send() failed and close_conn ran: it swallowed what
                # was left on the ORPHANED state dict, but the entries
                # already moved to `still` need the same treatment —
                # re-attaching them would strand futures nobody flushes.
                for _, f in still:
                    f.add_done_callback(
                        lambda fu: fu.cancelled() or fu.exception())
                return
        st["pending"] = still

    try:
        while not guard.triggered:
            # Only pending futures need the fast poll tick: buffered
            # output is event-driven — its socket sits in the writable
            # set, and select wakes the instant the kernel can take
            # more, so a stalled peer costs zero spin.
            busy = any(s["pending"] for s in conns.values())
            try:
                ready, writable, _ = select.select(
                    [srv] + list(conns),
                    [s for s, st in conns.items() if st["out"]], [],
                    0.005 if busy else 0.1)
            except (OSError, ValueError):
                break
            for sock in writable:
                pump_out(sock)
            for sock in ready:
                if sock is srv:
                    try:
                        c, _ = srv.accept()
                        c.setblocking(False)  # sends buffer, never stall
                        conns[c] = {"buf": b"", "out": bytearray(),
                                    "out_ofs": 0, "pending": deque()}
                    except OSError:
                        pass
                    continue
                st = conns.get(sock)
                if st is None:
                    continue
                try:
                    chunk = sock.recv(1 << 16)
                except (BlockingIOError, InterruptedError):
                    continue  # spurious wakeup on a non-blocking sock
                except OSError:
                    chunk = b""
                if not chunk:
                    close_conn(sock)  # peer EOF
                    continue
                *lines, st["buf"] = (st["buf"] + chunk).split(b"\n")
                for raw in lines:
                    if sock not in conns:
                        # handle_line condemned the connection (send
                        # failure, out-buffer overflow): the rest of
                        # this pipelined chunk has nobody to answer to
                        # — submitting it would strand futures on the
                        # orphaned state dict past close_conn's sweep.
                        break
                    if raw.strip():
                        handle_line(sock, st, raw.decode("utf-8", "replace"))
            for sock in list(conns):
                if sock in conns:
                    flush(sock, conns[sock])
            beat()
        # SIGTERM drain (the PR-2 preemption contract): stop accepting,
        # flush in-flight for the grace window, typed straggler lines.
        n_pending = sum(len(s["pending"]) for s in conns.values())
        if guard.triggered and n_pending:
            log(f"[serve] SIGTERM: draining {n_pending} in-flight "
                f"socket request(s) (timeout {drain_timeout:.1f}s)")
            deadline = time.monotonic() + max(0.0, drain_timeout)
            while (any(s["pending"] for s in conns.values())
                   and time.monotonic() < deadline):
                for sock in list(conns):
                    if sock in conns:
                        flush(sock, conns[sock])
                        pump_out(sock)
                time.sleep(0.02)
            for sock in list(conns):
                st = conns.get(sock)
                if st is None:
                    continue
                flush(sock, st)
                for rid, fut in st["pending"]:
                    fut.cancel()
                    send(sock, wire.error_record(
                        rid, "drain timeout: engine shutting down "
                        "before this request finished"))
                st["pending"] = deque()
        # Flush buffered response bytes before the finally closes the
        # sockets — a typed straggler line still sitting in an out
        # buffer is a silent drop from the peer's point of view.
        flush_deadline = time.monotonic() + 2.0
        while (any(s["out"] for s in conns.values())
               and time.monotonic() < flush_deadline):
            try:
                _, writable, _ = select.select(
                    [], [s for s, st in conns.items() if st["out"]],
                    [], 0.05)
            except (OSError, ValueError):
                break
            for sock in writable:
                pump_out(sock)
    finally:
        for sock in list(conns):
            close_conn(sock)
        try:
            srv.close()
        except OSError:
            pass
        if ready_file:
            try:
                os.remove(ready_file)  # a dead replica must not look ready
            except OSError:
                pass
    return served


def _parse_dtypes(spec: str):
    """--serve-dtypes 'fp32,bf16,int8' -> validated ladder tags (fp32
    always present and always the default rung)."""
    from tpuic.quant import DTYPE_TAGS
    tags = [t.strip() for t in (spec or "fp32").split(",") if t.strip()]
    for t in tags:
        if t not in DTYPE_TAGS:
            raise SystemExit(f"serve: --serve-dtypes: unknown dtype {t!r} "
                             f"(supported: {', '.join(DTYPE_TAGS)})")
    if "fp32" not in tags:
        tags.insert(0, "fp32")
    return tuple(dict.fromkeys(tags))


def _ladder_variants(model, variables, tags, size, *, mean, std, log):
    """Build the quantized rungs + run the accuracy gate (docs/
    performance.md, "Quantized serving"): a rung whose top-1 agreement
    with fp32 on the pinned synthetic eval set falls below the
    committed epsilon is REFUSED at startup — a quantization bug must
    fail the server loudly, not silently serve degraded predictions."""
    import jax

    from tpuic import quant
    variants = quant.serve_variants(model, variables, tags,
                                    normalize=True, mean=mean, std=std)
    if len(tags) > 1:
        imgs = quant.eval_images(128, size)
        ref_fwd, ref_vars = variants["fp32"]
        ref = jax.jit(ref_fwd)
        floor = 1.0 - quant.DEFAULT_EPSILON
        for tag in tags:
            if tag == "fp32":
                continue
            fwd, qv = variants[tag]
            agree = quant.top1_agreement(ref, ref_vars, jax.jit(fwd), qv,
                                         imgs)
            if agree < floor:
                raise SystemExit(
                    f"serve: dtype ladder rung {tag!r} FAILED the "
                    f"accuracy gate: top-1 agreement with fp32 is "
                    f"{agree:.4f} < {floor:.4f} on the pinned eval set "
                    f"(epsilon {quant.DEFAULT_EPSILON}) — refusing to "
                    "serve a quantization that moves predictions")
            log(f"dtype ladder rung {tag}: top-1 agreement "
                f"{agree:.4f} >= {floor:.4f} (accuracy gate OK)")
    return variants


# One swap at a time per process: the gate + stage + flip sequence is
# itself atomic from the operator's view, and a second candidate racing
# the first would gate against a moving incumbent.  Created at import
# (lazy creation would itself race two first swaps into separate locks).
_SWAP_LOCK = threading.Lock()


def _swap_context(engine, *, model, model_name: str, num_classes: int,
                  resize: int, tags, mean, std, ckpt_dir: str,
                  track: str) -> None:
    """Attach everything a later ``{"op": "swap"}`` control line needs
    to rebuild and gate a candidate ladder for THIS engine (model
    architecture, ladder tags, normalize stats, default checkpoint
    location).  Engines built outside this CLI (tests, embedders)
    simply have no context and refuse swap lines with a typed error."""
    engine.tpuic_swap_ctx = {
        "model": model, "model_name": model_name,
        "num_classes": int(num_classes), "resize": int(resize),
        "tags": tuple(tags), "mean": mean, "std": std,
        "ckpt_dir": ckpt_dir, "track": track,
    }


def _gate_outputs(engine, tree, imgs, tag: str):
    """Candidate outputs for one rung: through the engine's live AOT
    executables when the candidate is aval-identical (zero compiles —
    the hot-swap case the soak pins), else a one-off jit of the rung's
    forward (the aval-mismatch case prewarms executables in
    swap_weights anyway, so the gate compile is not the anomaly)."""
    try:
        return engine.candidate_outputs(tree, imgs, variant=tag)
    except ValueError:
        import jax
        fwd = engine._variants[tag][0]
        arr = np.asarray(imgs, engine.input_dtype)
        return jax.jit(fwd)(jax.device_put(tree), arr)


def run_swap(engine, req: dict, log) -> dict:
    """Gate + stage + flip for one ``{"op": "swap", ...}`` control line
    (docs/serving.md, "Model lifecycle: hot-swap, canary, rollback").

    Candidate source: ``{"ckpt_dir", "track"}`` (defaults: the serving
    checkpoint location) loads through the STRICT verified path
    (checkpoint/loading.py ``load_candidate_variables`` — CRC/manifest
    mandatory, no ladder fallback, typed ``swap_corrupt`` refusal), or
    ``{"synthetic_seed": N}`` re-inits the architecture from a seed
    (the load-test / soak candidate, no artifact to verify).

    Pre-flip admission gates, in order:

    1. **Integrity** — the candidate's bytes match its commit manifest
       (``swap_corrupt`` refusal; checkpoint candidates only).
    2. **Pinned-eval accuracy** — the candidate's fp32 outputs are
       finite on the pinned synthetic eval set (tpuic/quant
       ``eval_images``), and every configured dtype-ladder rung built
       from the candidate agrees with the candidate's own fp32 top-1
       within the committed epsilon — the PR-13 startup gate re-run
       per swap (``swap_accuracy`` refusal).  Gate evaluation rides the
       live generation's executables (``engine.candidate_outputs``):
       zero new compiles for aval-identical candidates.
    3. The flip itself is ``engine.swap_weights`` — the whole ladder as
       one unit, zero-drain by construction.

    A refused candidate never touches traffic: the incumbent keeps
    serving, untouched, and the caller gets the typed verdict.
    Raises ``SwapRejected`` / ``ValueError``; returns the
    ``swap_result`` record on success."""
    from tpuic.serve.admission import SwapRejected
    ctx = getattr(engine, "tpuic_swap_ctx", None)
    if ctx is None:
        raise ValueError("swap unsupported: this engine was built "
                         "without a swap context")
    if not _SWAP_LOCK.acquire(blocking=False):
        raise RuntimeError("swap already in progress — one candidate "
                           "at a time")
    try:
        import jax
        import jax.numpy as jnp

        from tpuic import quant
        from tpuic.checkpoint.loading import load_candidate_variables
        from tpuic.config import (Config, DataConfig, ModelConfig,
                                  OptimConfig, RunConfig)
        resize, tags = ctx["resize"], ctx["tags"]
        default = tags[0]
        if req.get("synthetic_seed") is not None:
            seed = int(req["synthetic_seed"])
            variables = ctx["model"].init(
                jax.random.key(seed),
                jnp.zeros((1, resize, resize, 3), jnp.float32),
                train=False)
            source = f"synthetic:{seed}"
        else:
            ckpt_dir = str(req.get("ckpt_dir") or ctx["ckpt_dir"] or "")
            if not ckpt_dir:
                raise ValueError(
                    "swap line needs 'ckpt_dir' (or 'synthetic_seed')")
            track = str(req.get("track") or ctx["track"] or "best")
            cfg = Config(
                data=DataConfig(data_dir=".", resize_size=resize),
                model=ModelConfig(name=ctx["model_name"],
                                  num_classes=ctx["num_classes"]),
                optim=OptimConfig(
                    ema_decay=_sidecar_ema(ckpt_dir, ctx["model_name"])),
                run=RunConfig(ckpt_dir=ckpt_dir))
            _, variables, _ = load_candidate_variables(
                cfg, track=track, log=log)
            source = os.path.join(ckpt_dir, ctx["model_name"], track)
        # Rebuild the dtype ladder FROM the candidate (the ladder swaps
        # as one unit — engine.swap_weights enforces the tag set).
        trees = {default: variables}
        for tag in tags[1:]:
            if tag == "bf16":
                trees[tag] = quant.bf16_variables(variables)
            elif tag == "int8":
                trees[tag] = quant.quantize_variables(variables)
            else:
                raise ValueError(f"unknown ladder rung {tag!r}")
        # Pinned-eval accuracy gate (pre-flip, off the request path).
        imgs = quant.eval_images(128, resize)
        ref = _gate_outputs(engine, trees[default], imgs, default)
        ref_probs, ref_order = (np.asarray(ref[0]), np.asarray(ref[1]))
        if not np.isfinite(ref_probs).all():
            raise SwapRejected(
                f"swap candidate {source} produced non-finite outputs "
                "on the pinned eval set — refusing to flip garbage "
                "into traffic", cause="swap_accuracy")
        floor = 1.0 - quant.DEFAULT_EPSILON
        for tag in tags[1:]:
            out_t = _gate_outputs(engine, trees[tag], imgs, tag)
            t_probs, t_order = (np.asarray(out_t[0]), np.asarray(out_t[1]))
            agree = float(np.mean(ref_order[:, 0] == t_order[:, 0]))
            if not np.isfinite(t_probs).all() or agree < floor:
                raise SwapRejected(
                    f"swap candidate {source} rung {tag!r} FAILED the "
                    f"accuracy gate: top-1 agreement with the "
                    f"candidate's fp32 is {agree:.4f} < {floor:.4f} on "
                    f"the pinned eval set (epsilon "
                    f"{quant.DEFAULT_EPSILON})", cause="swap_accuracy")
        res = engine.swap_weights(
            trees[default],
            variants={t: trees[t] for t in tags[1:]})
        how = ("executables reused" if res["reused_executables"]
               else f"{res['prewarmed']} executables prewarmed")
        log(f"[serve] hot-swap OK: {source} -> generation "
            f"{res['generation']} digest {res['digest']} ({how}, "
            f"{res['duration_s'] * 1000:.0f} ms)")
        return {"op": "swap_result", "ok": True, "source": source, **res}
    finally:
        _SWAP_LOCK.release()


def submit_swap(engine, req: dict, log):
    """Run the swap gate + flip on a worker thread, returning a Future
    that resolves to the ``swap_result`` record (or the typed verdict).

    Both transports ride their existing completion machinery: the
    future joins the pending deque like any request, so the accept /
    select loop keeps serving traffic and answering pings while the
    candidate loads and gates — the whole point of a ZERO-downtime
    lifecycle.  (A checkpoint load inside the select loop would stall
    pings past the router's window and read as a wedge.)"""
    fut = _FutFuture()

    def _worker() -> None:
        try:
            fut.set_result(run_swap(engine, req, log))
        except BaseException as e:
            fut.set_exception(e)

    threading.Thread(target=_worker, daemon=True,
                     name="tpuic-swap").start()
    return fut


def _sidecar_ema(ckpt_dir: str, model_name: str) -> float:
    """ema_decay from a checkpoint dir's config.json sidecar (0.0 when
    absent/corrupt — the same lenient rule build_engine applies)."""
    try:
        with open(os.path.join(ckpt_dir, model_name, "config.json")) as f:
            return float(
                json.load(f).get("optim", {}).get("ema_decay", 0.0))
    except (OSError, ValueError, TypeError):
        return 0.0


def build_engine(args):
    """Checkpoint -> warmed InferenceEngine (shared predict loading rules)."""
    # Persistent XLA compilation cache: warmup's per-bucket AOT compiles
    # land on disk, so a server RESTART warms up from cache instead of
    # recompiling. JAX_COMPILATION_CACHE_DIR wins over the flag.
    import jax

    from tpuic.compiled.cache import enable_compile_cache
    cache = enable_compile_cache(args.compile_cache_dir)
    dev = jax.devices()[0]
    print(f"[serve] {jax.device_count()} {dev.platform} device(s), "
          f"device_kind={dev.device_kind!r}; compile cache: {cache}",
          file=sys.stderr)

    from tpuic.checkpoint.loading import load_inference_variables
    from tpuic.config import (Config, DataConfig, ModelConfig, OptimConfig,
                              RunConfig)
    from tpuic.predict import resolve_model_auto
    from tpuic.serve import InferenceEngine

    if args.synthetic_init:
        # Seeded random init, no checkpoint: the load-testing / router-
        # soak replica mode.  Every replica built from the same seed
        # carries IDENTICAL weights, so a failover replay on a survivor
        # returns the same prediction the dead replica would have.
        import jax
        import jax.numpy as jnp

        from tpuic.models import create_model
        if args.model == "auto" or args.num_classes <= 0:
            raise SystemExit("serve: --synthetic-init needs an explicit "
                             "--model and --num-classes (there is no "
                             "checkpoint to resolve them from)")
        resize = args.resize if args.resize is not None else 299
        model = create_model(args.model, args.num_classes, dtype="float32")
        variables = model.init(
            jax.random.key(0),
            jnp.zeros((1, resize, resize, 3), jnp.float32), train=False)
        dc = DataConfig(data_dir=".", resize_size=resize)
        tags = _parse_dtypes(getattr(args, "serve_dtypes", "fp32"))
        variants = _ladder_variants(
            model, variables, tags, resize, mean=dc.mean, std=dc.std,
            log=lambda m: print("[serve]", m, file=sys.stderr))
        engine = InferenceEngine(
            forward_fn=variants["fp32"][0], variables=variants["fp32"][1],
            image_size=resize, input_dtype=np.uint8,
            buckets=tuple(int(b) for b in args.buckets.split(",")),
            max_wait_ms=args.max_wait_ms, queue_size=args.queue_size,
            variants={k: v for k, v in variants.items() if k != "fp32"})
        t = engine.warmup()
        n_exe = sum(len(v) if isinstance(v, dict) else 1
                    for v in t.values())
        print(f"[serve] synthetic init ({args.model}); warmup compiled "
              f"{n_exe} bucket executables: {t}", file=sys.stderr)
        _swap_context(engine, model=model, model_name=args.model,
                      num_classes=args.num_classes, resize=resize,
                      tags=tags, mean=dc.mean, std=dc.std,
                      ckpt_dir=args.ckpt_dir, track=args.track)
        return engine, resize, args.num_classes, args.model

    model_name, num_classes, resize = args.model, args.num_classes, args.resize
    ema_decay = 0.0
    if model_name == "auto":
        saved = resolve_model_auto(args.ckpt_dir)
        model_name = saved["name"]
        num_classes = num_classes or saved["num_classes"]
        ema_decay = saved["ema_decay"]
        if resize is None:
            resize = saved["resize_size"]
        print(f"[serve] auto-resolved model '{model_name}' "
              f"(num_classes={num_classes}, resize={resize})",
              file=sys.stderr)
    elif not args.init_from:
        # Explicit --model: still honor THIS model's config.json sidecar
        # for ema_decay (same rule as tpuic.predict) — an EMA-trained
        # checkpoint must serve its EMA weights (the ones 'best' was
        # selected on), not silently fall back to the raw params.
        sidecar = os.path.join(args.ckpt_dir, model_name, "config.json")
        try:
            with open(sidecar) as f:
                ema_decay = float(
                    json.load(f).get("optim", {}).get("ema_decay", 0.0))
        except (OSError, ValueError, TypeError):
            # Absent or corrupt sidecar (non-atomic trainer write) falls
            # back to raw params, same as _class_names' fallback.
            pass
    if resize is None:
        resize = 299
    if num_classes <= 0:
        raise SystemExit("serve: --num-classes required (or --model auto "
                         "with a config.json sidecar)")
    cfg = Config(
        data=DataConfig(data_dir=".", resize_size=resize),
        model=ModelConfig(name=model_name, num_classes=num_classes),
        optim=OptimConfig(ema_decay=ema_decay),
        run=RunConfig(ckpt_dir=args.ckpt_dir, init_from=args.init_from),
    )
    model, variables = load_inference_variables(
        cfg, track=args.track, log=lambda *a: print("[serve]", *a,
                                                    file=sys.stderr))
    buckets = tuple(int(b) for b in args.buckets.split(","))
    # Raw uint8 in, normalize fused into the compiled forward (4x less
    # H2D than shipping float32 — the device_prep lesson).  The dtype
    # ladder (--serve-dtypes) adds bf16/int8 weight rungs behind the
    # startup accuracy gate; request lines select one with "dtype".
    tags = _parse_dtypes(getattr(args, "serve_dtypes", "fp32"))
    variants = _ladder_variants(
        model, variables, tags, resize, mean=cfg.data.mean,
        std=cfg.data.std,
        log=lambda m: print("[serve]", m, file=sys.stderr))
    engine = InferenceEngine(
        forward_fn=variants["fp32"][0], variables=variants["fp32"][1],
        image_size=resize, input_dtype=np.uint8,
        buckets=buckets, max_wait_ms=args.max_wait_ms,
        queue_size=args.queue_size,
        variants={k: v for k, v in variants.items() if k != "fp32"})
    t = engine.warmup()
    n_exe = sum(len(v) if isinstance(v, dict) else 1 for v in t.values())
    print(f"[serve] warmup compiled {n_exe} bucket executables: {t}",
          file=sys.stderr)
    _swap_context(engine, model=model, model_name=model_name,
                  num_classes=num_classes, resize=resize, tags=tags,
                  mean=cfg.data.mean, std=cfg.data.std,
                  ckpt_dir=args.ckpt_dir, track=args.track)
    return engine, resize, num_classes, model_name


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="Dynamic-batching inference server (stdin JSONL or "
                    "directory watch)")
    p.add_argument("--ckpt-dir", default="dtmodel/cp")
    p.add_argument("--model", default="auto")
    p.add_argument("--num-classes", type=int, default=0)
    p.add_argument("--resize", type=int, default=None)
    p.add_argument("--track", default="best", choices=("best", "latest"))
    p.add_argument("--init-from", default="",
                   help="torch checkpoint instead of a tpuic one")
    p.add_argument("--buckets", default="1,8,32,128",
                   help="padding-bucket ladder (comma list)")
    p.add_argument("--serve-dtypes", default="fp32",
                   help="dtype ladder (comma list of fp32,bf16,int8): "
                        "per-dtype AOT executables share the bucket "
                        "cache; bf16 halves and int8 quarters weight "
                        "HBM (absmax per-channel, tpuic/quant). Each "
                        "quantized rung must pass the startup top-1 "
                        "accuracy gate vs fp32 on the pinned eval set "
                        "or the server refuses to start. Request lines "
                        "pick a rung with \"serve_dtype\"; default is "
                        "fp32")
    p.add_argument("--max-wait-ms", type=float, default=5.0)
    p.add_argument("--queue-size", type=int, default=256)
    p.add_argument("--compile-cache-dir", default="",
                   help="persistent XLA compile cache (restarts warm up "
                        "from disk) when JAX_COMPILATION_CACHE_DIR is "
                        "unset; default: the checkout's tests/.jax_cache")
    p.add_argument("--top-k", type=int, default=1)
    p.add_argument("--classes", default="",
                   help="optional file of class names, one per line")
    p.add_argument("--watch", default="",
                   help="watch this directory for images instead of stdin")
    p.add_argument("--poll-s", type=float, default=0.5)
    p.add_argument("--once", action="store_true",
                   help="with --watch: process current files, then exit")
    p.add_argument("--listen", default="",
                   help="serve socket JSONL on HOST:PORT instead of "
                        "stdin (port 0 = kernel-assigned; the replica "
                        "transport behind python -m tpuic.serve.router)")
    p.add_argument("--ready-file", default="",
                   help="with --listen: atomically write {port, pid, "
                        "prom_port} here once the engine is warmed and "
                        "the socket is bound — the router's port "
                        "handoff")
    p.add_argument("--synthetic-init", action="store_true",
                   help="seeded random init instead of a checkpoint "
                        "(load testing / router-soak replicas; requires "
                        "explicit --model and --num-classes)")
    p.add_argument("--out", default="", help="output JSONL (default stdout)")
    p.add_argument("--drain-timeout", type=float, default=30.0,
                   help="on SIGTERM/SIGINT, wait up to this many seconds "
                        "for in-flight requests before failing stragglers "
                        "with an error line and exiting")
    p.add_argument("--prom-port", type=int, default=0,
                   help="serve a Prometheus /metrics endpoint on this "
                        "port (queue wait, pad efficiency, latency "
                        "percentiles from the shared meter; 0 disables; "
                        "-1 binds a kernel-assigned free port — the "
                        "resolved port lands in --ready-file, how "
                        "router replicas expose their health signals "
                        "without port races)")
    p.add_argument("--prom-host", default="127.0.0.1",
                   help="interface for --prom-port (loopback by default "
                        "— the endpoint is unauthenticated; bind "
                        "0.0.0.0 only behind a firewall)")
    p.add_argument("--prom-dump", default="",
                   help="write the Prometheus text exposition to this "
                        "file on shutdown (and each poll tick under "
                        "--watch) — the textfile-collector transport")
    p.add_argument("--slo", default="",
                   help="latency SLOs, comma list of "
                        "'serve_latency:pQ<=Nms[@target]' specs "
                        "(telemetry/slo.py). Subscribing the tracker is "
                        "what switches per-request span events on; "
                        "attainment and error-budget burn land in the "
                        "Prometheus exposition and the final stats line")
    p.add_argument("--admission", action="store_true",
                   help="SLA-aware admission control (docs/serving.md): "
                        "request lines may carry priority/deadline_ms/"
                        "tenant; a full queue rejects with a typed, "
                        "cause-labeled error line instead of blocking "
                        "the accept loop, higher priority classes are "
                        "batched first (and evict lower ones from a "
                        "full queue), and expired deadlines shed at "
                        "pop time")
    p.add_argument("--quota", action="append", default=[],
                   metavar="TENANT=RPS",
                   help="per-tenant token-bucket quota in requests/sec "
                        "(repeatable, or one comma list); '*=RPS' sets "
                        "the shared free pool unconfigured tenants and "
                        "dry tenant buckets draw from. Implies "
                        "--admission")
    p.add_argument("--brownout-slo", default="",
                   help="name of one --slo objective (e.g. "
                        "serve_latency_p99) whose error-budget burn "
                        "rate drives brownout: past --brownout-tighten "
                        "the controller sheds one priority class per "
                        "SLO report, recovering hysteretically below "
                        "--brownout-recover. Implies --admission")
    p.add_argument("--brownout-tighten", type=float, default=2.0,
                   help="burn rate at/above which brownout tightens "
                        "one level")
    p.add_argument("--brownout-recover", type=float, default=1.0,
                   help="burn rate at/below which (after 3 consecutive "
                        "reports) brownout relaxes one level")
    args = p.parse_args(argv)
    if args.quota or args.brownout_slo:
        args.admission = True

    slo_tracker = None
    if args.slo:
        # Parse BEFORE the checkpoint load + AOT warmup — a typo'd
        # objective must fail the command line, not minutes in.
        from tpuic.telemetry.slo import SLOTracker, parse_objectives
        try:
            slo_tracker = SLOTracker(parse_objectives(
                args.slo, allowed=("serve_latency",)))
        except ValueError as e:
            raise SystemExit(f"serve: --slo: {e}")

    # Admission config parses up front too (same fail-fast rule): a
    # typo'd quota would read as "unlimited" exactly when you meant to
    # cap someone, and a brownout coupled to an objective --slo never
    # tracks would silently never tighten.
    admission_ctl = None
    if args.admission:
        from tpuic.serve.admission import (AdmissionController,
                                           BrownoutController, parse_quotas)
        try:
            quotas = parse_quotas(args.quota)
        except ValueError as e:
            raise SystemExit(f"serve: --quota: {e}")
        brownout = None
        if args.brownout_slo:
            known = ([o.name for o in slo_tracker.objectives]
                     if slo_tracker is not None else [])
            if args.brownout_slo not in known:
                raise SystemExit(
                    f"serve: --brownout-slo {args.brownout_slo!r} names "
                    f"no --slo objective (configured: "
                    f"{', '.join(known) or 'none'}) — brownout would "
                    "never see a burn rate")
            brownout = BrownoutController(
                args.brownout_slo, tighten_above=args.brownout_tighten,
                recover_below=args.brownout_recover)
        admission_ctl = AdmissionController(quotas, brownout=brownout)

    # Install the latch BEFORE the (potentially minutes-long) checkpoint
    # load + AOT warmup: an eviction during startup must also exit
    # cleanly, not dump a traceback from inside a compile.
    import signal

    from tpuic.runtime.preemption import PreemptionGuard
    guard = PreemptionGuard(signals=(signal.SIGTERM,)).install()

    if args.classes and not os.path.isfile(args.classes):
        # Validate BEFORE the checkpoint load + per-bucket AOT warmup —
        # a typo'd path must not cost minutes of startup first.
        raise SystemExit(f"serve: --classes file not found: {args.classes}")
    engine, size, num_classes, model_name = build_engine(args)
    names = _class_names(args.ckpt_dir, model_name, num_classes,
                         args.classes)

    # Prometheus exposition (telemetry/prom.py): counters come straight
    # from engine.stats — the shared LatencyMeter percentiles, pad
    # efficiency, bucket histogram, compile counts.
    from tpuic.telemetry.prom import (PromServer, serve_exposition,
                                      write_exposition)

    # Supervised liveness (runtime/supervisor.py, docs/robustness.md):
    # under `python -m tpuic.supervise` the parent sets the heartbeat
    # env; mirror engine activity (serve_batch events) into the file AND
    # tick it from the accept loop — an idle server with no requests is
    # alive, and the watchdog must see that, not a stale file. The
    # flight recorder (telemetry/flight.py) registers its SIGQUIT dump
    # FIRST so the faulthandler stack dump chains into it: the
    # supervisor's hang escalation then captures stacks + the event
    # timeline (serve_batch/admission/slo — memory samples are
    # scrape-side only here, see the sampler below) leading into the
    # wedge.
    from tpuic.runtime.supervisor import (HeartbeatWriter,
                                          install_stack_dump_handler)
    from tpuic.telemetry.flight import install_flight_recorder
    flight = install_flight_recorder()
    heartbeat = HeartbeatWriter.from_env()
    if heartbeat is not None or flight is not None:
        install_stack_dump_handler(chain=flight is not None)
    if heartbeat is not None:
        from tpuic.telemetry.events import bus as _bus
        _bus.subscribe(heartbeat)

    def _beat() -> None:
        if heartbeat is not None:
            heartbeat.beat()

    if slo_tracker is not None:
        # Attaching subscribes for 'serve_span' events, which is exactly
        # what turns the engine's per-request span publishing on
        # (engine._resolve checks bus.active("serve_span")).
        from tpuic.telemetry.events import bus as _slo_bus
        slo_tracker.attach(_slo_bus)

    if admission_ctl is not None:
        # Post-build attach (engine.admission is a public, settable
        # field): submit() now consults brownout + quotas up front.
        engine.admission = admission_ctl
        if admission_ctl.brownout is not None:
            # Brownout rides the same bus the SLO tracker publishes its
            # periodic reports on; its tighten/recover transitions come
            # back as 'admission' events (JSONL/TensorBoard sinks).
            from tpuic.telemetry.events import bus as _adm_bus
            admission_ctl.brownout.attach(_adm_bus)
        print(f"[serve] admission control on: "
              f"{json.dumps(admission_ctl.state())}", file=sys.stderr)

    # Device-memory accounting (telemetry/memory.py): sampled at scrape
    # time (each /metrics hit, each --prom-dump tick, and shutdown) —
    # the serve tier has no step boundary, and a scrape-time metadata
    # read is free of the request path entirely. Deliberately NOT
    # published to the bus: scrapes run in the PromServer thread at the
    # scraper's cadence, and the supervised-liveness heartbeat treats
    # any bus activity as proof of life — an external scraper must not
    # keep a wedged server looking alive to the watchdog.
    from tpuic.telemetry.memory import MemorySampler
    mem_sampler = MemorySampler(publish=lambda *a, **kw: None)

    def _prom_text() -> str:
        mem_sampler.sample()
        return serve_exposition(
            engine.stats.snapshot(),
            heartbeat_age_s=(heartbeat.age_s() if heartbeat is not None
                             else None),
            slo=(slo_tracker.report() if slo_tracker is not None
                 else None),
            admission=(admission_ctl.state() if admission_ctl is not None
                       else None),
            memory=mem_sampler.snapshot(),
            # Device-time attribution (telemetry/profile.py): the
            # largest bucket executable's roofline waterfall, scaled to
            # the span ledger's measured device phase — scrape-time
            # only, never on the request path.
            profile=engine.profile_waterfall())

    prom_server = None
    if args.prom_port:
        prom_server = PromServer(max(0, args.prom_port), _prom_text,
                                 host=args.prom_host)
        print(f"[serve] prometheus /metrics on "
              f"{args.prom_host}:{prom_server.port}", file=sys.stderr)
    # 'flood' injection point (runtime/faults.py): a synthetic
    # low-priority request storm from inside the process, at #PARAM
    # req/s — reproducible overload under the TPUIC_FAULTS grammar, so
    # the admission layer's shedding can be driven (and CI-soaked)
    # without an external load generator.  Storm futures retrieve their
    # own outcomes: sheds and rejections are the point, not log spam.
    import threading as _threading
    flood_stop = _threading.Event()
    if _faults.fire("flood"):
        flood_rate = _faults.param("flood")
        flood_rate = 50.0 if flood_rate is None else float(flood_rate)
        flood_img = np.zeros((1, size, size, 3), engine.input_dtype)

        def _flood() -> None:
            period = 1.0 / max(flood_rate, 1e-3)
            while not flood_stop.is_set() and not guard.triggered:
                try:
                    fut = engine.submit(flood_img, timeout=0,
                                        priority="low", tenant="_flood")
                    fut.add_done_callback(
                        lambda f: f.cancelled() or f.exception())
                except Exception:  # noqa: BLE001 — rejects ARE the test
                    pass
                flood_stop.wait(period)

        _threading.Thread(target=_flood, daemon=True,
                          name="tpuic-flood").start()
        print(f"[serve] fault 'flood' armed: synthetic low-priority "
              f"storm at {flood_rate:g} req/s", file=sys.stderr)

    k = max(1, min(args.top_k, num_classes))
    out = open(args.out, "w") if args.out else sys.stdout
    pending = deque()  # (id, Future) in submission order
    # Control futures (swap lines) drain OUT of order, in their own
    # lane: a checkpoint load + gate takes seconds, and the in-order
    # traffic drain must not head-of-line block every predict answered
    # behind it (responses are keyed by id — order is not part of the
    # control contract).
    control_pending = deque()
    served = 0

    def emit(rid, probs, order) -> None:
        nonlocal served
        out.write(json.dumps(_result_record(rid, probs, order,
                                            names, k)) + "\n")
        out.flush()
        served += 1

    def emit_outcome(rid, res) -> None:
        """One resolved future: a (probs, order) result emits the usual
        record; a dict is a control-line outcome (swap_result) and is
        written as-is — not counted as served traffic."""
        if isinstance(res, dict):
            out.write(json.dumps({**res, "id": rid}) + "\n")
            out.flush()
        else:
            emit(rid, res[0], res[1])

    def drain_control(block: bool = False, deadline: float = None
                      ) -> None:
        """Emit completed control-line outcomes, any order (responses
        are keyed by id — control order is not part of the contract,
        and a seconds-long swap must never head-of-line block traffic
        results).  ``block`` waits each out, bounded by ``deadline``;
        past it the straggler gets an explicit error line — the same
        never-a-silent-drop rule as drain()."""
        still = deque()
        while control_pending:
            rid, fut = control_pending.popleft()
            if not fut.done():
                if not block:
                    still.append((rid, fut))
                    continue
                # Same escalation discipline as drain(): the
                # no-deadline wait polls in short slices re-checking
                # the SIGTERM latch (PEP 475 would resume a bare
                # result() right through the signal — a wedged swap
                # worker would make the server unkillable), and the
                # latch converts the wait into a --drain-timeout
                # deadline.
                if deadline is None:
                    while not fut.done() and not guard.triggered:
                        try:
                            fut.result(timeout=0.5)
                        except (TimeoutError, _FutTimeout):
                            pass
                        except Exception:  # noqa: BLE001
                            break  # done with an exception: read below
                    if not fut.done() and guard.triggered:
                        deadline = (time.monotonic()
                                    + max(0.0, args.drain_timeout))
                try:
                    if deadline is not None and not fut.done():
                        fut.result(timeout=max(
                            0.0, deadline - time.monotonic()))
                except (TimeoutError, _FutTimeout):
                    fut.cancel()
                    out.write(wire.error_line(
                        rid, "drain timeout: swap unresolved at "
                        "shutdown"))
                    out.flush()
                    continue
                except Exception:  # noqa: BLE001 — read below
                    pass
            if fut.cancelled():
                out.write(wire.error_line(rid, "cancelled"))
                out.flush()
            elif fut.exception() is not None:
                out.write(wire.error_line(rid, fut.exception()))
                out.flush()
            else:
                emit_outcome(rid, fut.result())
        control_pending.extend(still)

    def drain(block: bool, deadline: float = None) -> None:
        """Emit completed responses; ``block`` waits for stragglers, up to
        ``deadline`` (time.monotonic()). Past the deadline, requests the
        device DID finish still emit their results (in submission order);
        only genuinely unresolved ones get an explicit error line — never
        a silent drop, never a discarded finished result.

        The no-deadline blocking wait polls in short slices re-checking
        the SIGTERM latch: a plain ``fut.result()`` is resumed after
        signals (PEP 475), so a SIGTERM arriving while draining a wedged
        request at EOF would otherwise never be observed — the latch
        escalates the wait to a ``--drain-timeout`` deadline instead."""
        drain_control()  # opportunistic; the blocking pass runs last
        while pending and (block or pending[0][1].done()):
            rid, fut = pending.popleft()
            try:
                if block and deadline is None:
                    while not fut.done() and not guard.triggered:
                        try:
                            fut.result(timeout=0.5)
                        except (TimeoutError, _FutTimeout):
                            pass
                    if not fut.done() and guard.triggered:
                        # Escalate: persists for the remaining stragglers
                        # (``deadline`` is function-local).
                        deadline = (time.monotonic()
                                    + max(0.0, args.drain_timeout))
                if deadline is None:
                    res = fut.result()
                else:
                    res = fut.result(
                        timeout=max(0.0, deadline - time.monotonic()))
            except (TimeoutError, _FutTimeout):
                pending.appendleft((rid, fut))
                expired = list(pending)
                pending.clear()
                for srid, sfut in expired:
                    if sfut.done() and not sfut.cancelled():
                        try:
                            sres = sfut.result()
                        except Exception as e:  # noqa: BLE001
                            out.write(wire.error_line(srid, e))
                        else:
                            emit_outcome(srid, sres)
                        continue
                    sfut.cancel()  # not-yet-dispatched may still cancel
                    out.write(wire.error_line(
                        srid, "drain timeout: engine shutting down "
                        "before this request finished"))
                out.flush()
                drain_control(block=True, deadline=deadline)
                return
            except Exception as e:  # noqa: BLE001 — per-request error line
                # wire.error_line types the verdict (a pop-time
                # DeadlineExceeded shed, an eviction): cause + class
                # labels match the rejected_total counter — the one
                # encoder all three serve tiers share (wire.py).
                out.write(wire.error_line(rid, e))
                out.flush()
                continue
            except BaseException:
                # KeyboardInterrupt/SystemExit mid-wait: this request is
                # already popped — put it back so the handler's follow-up
                # drain still owns it (never a silent drop).
                pending.appendleft((rid, fut))
                raise
            emit_outcome(rid, res)
        if block:
            # Traffic drained in order; control outcomes last, bounded
            # by the same deadline.
            drain_control(block=True, deadline=deadline)

    def submit(rid: str, path: str, **sla) -> bool:
        """Decode + enqueue; False = decode failed (error line emitted).

        ``sla``: per-request ``priority``/``deadline_ms``/``tenant``
        from the request line.  With --admission the enqueue is
        non-blocking: a typed rejection (queue full / quota / brownout)
        becomes an immediate error line naming its cause instead of the
        accept loop stalling behind a flood."""
        try:
            img = _load_image(path, size)
        except Exception as e:  # noqa: BLE001
            out.write(wire.error_line(rid, f"decode: {e}"))
            out.flush()
            return False
        try:
            if engine.admission is not None:
                sla.setdefault("timeout", 0)
            pending.append((rid, engine.submit(img, **sla)))
        except AdmissionError as e:
            out.write(wire.error_line(rid, e))
            out.flush()
            return True  # the request was handled: verdict delivered
        except (ValueError, TypeError) as e:
            # Bad SLA fields (unknown priority, non-numeric deadline)
            # are the request's problem, not the server's.
            out.write(wire.error_line(rid, e))
            out.flush()
            return True
        drain(block=False)  # opportunistic: decode overlaps device work
        return True

    try:
        if args.listen:
            served = serve_socket(
                engine, listen=args.listen, names=names, top_k=k,
                size=size, guard=guard, beat=_beat,
                drain_timeout=args.drain_timeout,
                ready_file=args.ready_file,
                prom_port=(prom_server.port if prom_server is not None
                           else None),
                log=lambda msg: print(msg, file=sys.stderr))
        elif args.watch:
            exts = (".png", ".jpg", ".jpeg", ".bmp", ".gif", ".webp")
            seen: set = set()
            attempts: dict = {}
            while not guard.triggered:
                fresh = sorted(
                    f for f in os.listdir(args.watch)
                    if f.lower().endswith(exts) and f not in seen)
                for f in fresh:
                    if guard.triggered:
                        break  # stop ACCEPTING; in-flight drains below
                    if submit(f, os.path.join(args.watch, f)):
                        seen.add(f)
                        attempts.pop(f, None)
                    else:
                        # A file mid-copy decodes as truncated; retry on
                        # later ticks, give up (and stop re-erroring)
                        # after 3 — in --once mode immediately, there is
                        # no later tick.
                        attempts[f] = attempts.get(f, 0) + 1
                        if args.once or attempts[f] >= 3:
                            seen.add(f)
                drain(block=False)
                _beat()
                if args.prom_dump:
                    # Per-tick refresh: a textfile collector scraping the
                    # dump sees live counters, not only the final state.
                    # Guarded: monitoring must never take down serving
                    # (disk-full on the textfile path is not our outage).
                    try:
                        write_exposition(args.prom_dump, _prom_text())
                    except OSError as e:
                        print(f"[serve] prom dump failed: {e}",
                              file=sys.stderr)
                if args.once and not fresh and not pending:
                    break
                if args.once:
                    drain(block=True)
                    break
                time.sleep(args.poll_s)
        else:
            def handle(line: str) -> None:
                line = line.strip()
                if not line:
                    return
                try:
                    req = json.loads(line)
                    if not isinstance(req, dict):
                        raise TypeError("not an object")
                    if req.get("op") == "swap":
                        # Control line (docs/serving.md, "Model
                        # lifecycle"): gate + flip off-thread; the
                        # swap_result (or typed verdict) drains on the
                        # CONTROL lane, out of order — a seconds-long
                        # checkpoint load must not head-of-line block
                        # the in-order traffic drain behind it.
                        control_pending.append(
                            (str(req.get("id", "swap")),
                             submit_swap(engine, req,
                                         lambda m: print(
                                             m, file=sys.stderr))))
                        return
                    path = req["path"]
                except (ValueError, KeyError, TypeError):
                    out.write(wire.error_line(
                        None, f"bad request line: {line[:80]}"))
                    out.flush()
                    return
                # Optional SLA fields per request line — honored only
                # under --admission (docs/serving.md): without the
                # operator opt-in, a client self-assigning "high" could
                # evict other clients' queued requests on a server
                # whose policy is plain FIFO.
                sla = {}
                if engine.admission is not None:
                    sla = {k: req[k] for k in ("priority", "deadline_ms",
                                               "tenant") if req.get(k)
                           is not None}
                if req.get("serve_dtype") is not None:
                    # Ladder rung selection (serve_dtype, matching the
                    # socket transport; "dtype" is the wire array
                    # payload's element type); a typo'd rung gets a
                    # typed error line through submit()'s ValueError
                    # arm.
                    sla["dtype"] = str(req["serve_dtype"])
                submit(str(req.get("id", path)), path, **sla)

            # select()-gated RAW reads, not ``for line in sys.stdin``: a
            # signal handler only sets the latch and PEP 475 would resume
            # a blocked readline — an idle server would never observe
            # SIGTERM. With a select timeout the loop re-checks the latch
            # (and opportunistically drains) at least every 200 ms. Raw
            # os.read + explicit line splitting, because Python's stdin
            # buffering would hide burst-written lines from select (the
            # bytes sit in the TextIOWrapper, not at the fd) and stall
            # every request after the first. A non-fd stdin (tests feeding
            # a StringIO) can't select; it reads unguarded, the
            # pre-rewrite behavior.
            import select
            try:
                stdin_fd = sys.stdin.fileno()
            except (ValueError, OSError, AttributeError):
                stdin_fd = None
            if stdin_fd is None:
                for line in sys.stdin:
                    if guard.triggered:
                        break
                    handle(line)
            else:
                tail = b""
                while not guard.triggered:
                    try:
                        ready, _, _ = select.select([stdin_fd], [], [], 0.2)
                    except (OSError, ValueError):  # stdin closed under us
                        break
                    if not ready:
                        drain(block=False)
                        _beat()
                        continue
                    _beat()
                    chunk = os.read(stdin_fd, 1 << 16)  # ready: won't block
                    if not chunk:
                        break  # EOF
                    *lines, tail = (tail + chunk).split(b"\n")
                    for raw in lines:
                        handle(raw.decode("utf-8", "replace"))
                if tail.strip() and not guard.triggered:
                    handle(tail.decode("utf-8", "replace"))  # unterminated last line
        if guard.triggered:
            # Graceful preemption: everything already accepted drains for
            # up to --drain-timeout; stragglers get explicit error lines.
            print(f"[serve] SIGTERM: draining {len(pending)} in-flight "
                  f"request(s) (timeout {args.drain_timeout:.1f}s)",
                  file=sys.stderr)
            drain(block=True,
                  deadline=time.monotonic() + max(0.0, args.drain_timeout))
        else:
            drain(block=True)
    except KeyboardInterrupt:
        drain(block=True,
              deadline=time.monotonic() + max(0.0, args.drain_timeout))
    finally:
        guard.uninstall()
        flood_stop.set()
        engine.close(timeout=max(5.0, args.drain_timeout))
        if prom_server is not None:
            prom_server.close()
        if args.prom_dump:
            try:
                write_exposition(args.prom_dump, _prom_text())
                print(f"[serve] prometheus exposition -> {args.prom_dump}",
                      file=sys.stderr)
            except OSError as e:
                print(f"[serve] prom dump failed: {e}", file=sys.stderr)
        if slo_tracker is not None:
            print(f"[serve] slo: {slo_tracker.summary_line()}",
                  file=sys.stderr)
        if admission_ctl is not None:
            # Attribution companion to the [slo] line: the rejected_by
            # split says whether budget burn came from sheds (deadline /
            # brownout causes) or from slow service (no sheds, blown
            # attainment).  The FULL typed vocabulary is folded in —
            # zero-filled causes included — so a soak ledger attributes
            # every cause (replica_lost, the swap verdicts) from this
            # one line without grepping raw JSONL for causes that
            # happened not to fire.
            from tpuic.serve.admission import CAUSES
            snap = engine.stats.snapshot()
            rej = {c: snap["rejected_by"].get(c, {}) for c in CAUSES}
            rej.update({c: by for c, by in snap["rejected_by"].items()
                        if c not in rej})  # never drop an unknown cause
            print(f"[admission] state={json.dumps(admission_ctl.state())} "
                  f"rejected_by={json.dumps(rej)}",
                  file=sys.stderr)
        print(f"[serve] served {served} requests; stats: "
              f"{json.dumps(engine.stats.snapshot())}", file=sys.stderr)
        if out is not sys.stdout:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
