"""Ragged packed serving, in process, through `serve.server.Server.submit`.

Set-up makes the weights on the device in one jitted call from the seed,
boots the server (which warms its one packed executable), and sends one
block of warm-up requests through the whole path. The window then offers
`embed` requests open loop at the mix's fixed rate from one generator
thread of this process (only the process that holds the chip can trace
it), each timed from when it was due. Answers are compared, once the
window has closed, with the plain reference run on each sequence alone.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from benchmark import compare, flops, readers, span_readers, traffic
from benchmark.program import model_sizes, program_config
from benchmark.reference import proteinbert_f32 as ref
from benchmark.device import memory_peak_bytes

SAMPLE = 64


class _Load:
    """One open-loop generator thread and what it recorded."""

    def __init__(self, server, seqs, due, seconds):
        self.server, self.seqs, self.due, self.seconds = server, seqs, due, seconds
        self.futures, self.sent, self.done = [], [], {}
        self.refused = 0
        self.t0 = None
        self._thread = threading.Thread(target=self._run, name="benchmark-load")

    def start(self):
        self.t0 = time.perf_counter()
        self._thread.start()

    def join(self):
        self._thread.join()

    def _run(self):
        import jax

        t0, done = self.t0, self.done
        for i, (seq, due) in enumerate(zip(self.seqs, self.due)):
            if due >= self.seconds:
                break
            wait = due - (time.perf_counter() - t0)
            if wait > 0:
                time.sleep(wait)
            self.sent.append(time.perf_counter() - t0)
            try:
                with jax.profiler.TraceAnnotation("benchmark.load.submit"):
                    fut = self.server.submit("embed", seq)
            except Exception:  # a refusal is a failed request, counted
                self.refused += 1
                fut = None
            else:
                fut.add_done_callback(
                    lambda f, i=i: done.__setitem__(i, time.perf_counter() - t0))
            self.futures.append(fut)


def _tokens(seqs, width):
    """The benchmark's own tokenizer: <sos> residues <eos>, padded."""
    lut = np.full(256, 3, np.int32)
    for i, ch in enumerate(ref.ALPHABET):
        lut[ord(ch)] = ref.N_SPECIAL + i
    out = np.full((len(seqs), width), ref.PAD_ID, np.int32)
    for r, s in enumerate(seqs):
        ids = lut[np.frombuffer(s.encode("ascii"), np.uint8)]
        out[r, 0] = ref.SOS_ID
        out[r, 1:1 + len(ids)] = ids
        out[r, 1 + len(ids)] = ref.EOS_ID
    return out


def reference_answers(seed, seqs, ladder, m, precision="f32", rows=16,
                      bf16_operands=True):
    """The plain reference's answer to each sequence, alone in a row of
    the smallest span of the ladder that holds it. Where the
    configuration states bfloat16 for the served model's products, the
    reference's weights enter them rounded to bfloat16; everything else
    is float32."""
    import jax
    from functools import partial

    params = jax.jit(partial(ref.init_params, m=m))(ref.seed_key(seed))
    if bf16_operands:
        params = ref.round_product_weights(params)
    ladder = np.asarray(ladder)
    spans = ladder[np.searchsorted(ladder, [len(s) + 2 for s in seqs])]
    answers = [None] * len(seqs)
    for span in sorted(set(spans.tolist())):
        idx = [i for i, s in enumerate(spans) if s == span]
        for lo in range(0, len(idx), rows):
            part = idx[lo:lo + rows]
            toks = _tokens([seqs[i] for i in part], span)
            toks = np.concatenate(
                [toks, np.tile(toks[-1:], (rows - len(part), 1))])
            got = jax.device_get(ref.embed_rows(params, toks, m, precision))
            for j, i in enumerate(part):
                answers[i] = {k: got[k][j] for k in got}
    return answers


def _capture_telemetry():
    """A Telemetry that keeps each request's stage times in memory."""
    from proteinbert_tpu.obs import Telemetry

    class Capture(Telemetry):
        def __init__(self):
            super().__init__(metrics=False)
            self.stages = []

        def emit(self, event, **fields):
            if event == "serve_request" and fields.get("stages"):
                self.stages.append(fields["stages"])
            return None

        def dump_flight(self, reason):
            return None

    return Capture()


def run(run, devices):
    """One run of the cell: the window, then the comparison of a sample
    of its answers with the plain reference."""
    out, sample = measure(run, devices)
    t_ref = time.perf_counter()
    reference = reference_answers(
        run.seed, sample["seqs"], sample["ladder"], model_sizes(run.config),
        rows=run.workload["reference_rows"],
        bf16_operands=run.config["dtype"] == "bfloat16")
    print(f"reference: {len(reference)} answers in "
          f"{time.perf_counter() - t_ref:.1f} s")
    gaps = compare.embedding_checks(sample["served"], reference)
    out["checks"] = [(name, gaps[name], run.workload["limits"][name])
                     for name in sorted(gaps)]
    return out


def measure(run, devices):
    """Set-up and the measured window, with the server closed and its
    state freed on return: (the run's result without its checks, the
    sample of the window's answers the reference is held against). The
    knee sweep (`benchmark.find_knee`) stops here."""
    import jax

    from proteinbert_tpu.models import proteinbert
    from proteinbert_tpu.serve.server import Server

    wl, mix = run.workload, run.mix
    cfg = program_config(run.config, wl["overrides"])
    m = model_sizes(run.config)
    ladder = list(cfg.data.buckets)
    opts = dict(wl["server"])
    segments, seq_len = opts["pack_max_segments"], cfg.data.seq_len

    n_blocks = traffic.blocks_for(mix, run.seconds)
    seqs, lengths = traffic.sequences(mix, n_blocks, run.seed)
    due = traffic.due_times(mix, n_blocks, run.seed)
    warm_seqs, _ = traffic.sequences(mix, wl["warm_blocks"], run.seed, stream=4)

    params = jax.jit(proteinbert.init, static_argnames="cfg")(
        ref.seed_key(run.seed), cfg.model)
    tele = _capture_telemetry() if run.trace else None
    server = Server(params, cfg, buckets=ladder, warm_kinds=("embed",),
                    telemetry=tele, trace_sample_rate=1.0 if run.trace else None,
                    **opts)
    server.start()
    ladder_rows = sorted(int(c) for c in server.dispatcher.batch_classes)
    try:
        booted = server.stats()["batched_rows"]
        for f in [server.submit("embed", s) for s in warm_seqs]:
            f.result(timeout=300)
        # The scheduler counts a batch after it has answered its riders:
        # wait until the warm-up's last batch is counted.
        before = server.stats()
        while before["batched_rows"] - booted < len(warm_seqs):
            time.sleep(0.01)
            before = server.stats()
        if tele is not None:
            tele.stages.clear()
        load = _Load(server, seqs, due, run.seconds)
        with run.window():
            load.start()
            load.join()
            time.sleep(max(0.0, run.seconds - (time.perf_counter() - load.t0)))
            # Read before the window closes: a traced run then spends
            # seconds on its trace while the server goes on dispatching.
            after = server.stats()
            stages = list(tele.stages) if tele is not None else None
        memory_peak = memory_peak_bytes(devices)
        if wl["judged"] == "latency":
            # Every request due in the window is waited for: its latency
            # counts wherever it ends.
            deadline = time.perf_counter() + wl["straggler_seconds"]
            for f in load.futures:
                if f is not None and not f.done():
                    try:
                        f.result(timeout=max(0.0, deadline - time.perf_counter()))
                    except Exception:
                        pass
        closed = time.perf_counter() - load.t0
    finally:
        server.close(drain=False)

    n = len(load.futures)
    ok = np.array([f is not None and f.done() and f.exception() is None
                   for f in load.futures])
    done_at = np.array([load.done.get(i, np.inf) for i in range(n)])
    due_n, lengths_n = due[:n], lengths[:n]
    in_window = ok & (done_at <= run.seconds)
    errors = sum(1 for f in load.futures
                 if f is None or (f.done() and f.exception() is not None
                                  and not _aborted(f)))
    latency = np.where(ok, done_at, closed) - due_n       # seconds
    e2e = {}
    if wl["judged"] == "latency":
        failed = int(n - ok.sum())
        # the tail of ALL the requests due in the window, stalls and all
        e2e["embed_latency_p95_ms"] = float(np.percentile(latency, 95) * 1e3)
    else:
        failed = int(errors)
        e2e["embed_residues_per_s"] = float(
            lengths_n[in_window].sum() / run.window_s)

    # A sample of the window's answers, the longest among them, against
    # the reference on each sequence alone.
    pool = np.flatnonzero(in_window if in_window.any() else ok)
    rng = np.random.default_rng([run.seed, 5])
    pick = set(rng.choice(pool, min(SAMPLE - 1, len(pool)), replace=False).tolist())
    pick.add(int(pool[np.argmax(lengths_n[pool])]))
    pick = sorted(pick)
    sample = {"seqs": [seqs[i] for i in pick], "ladder": ladder,
              "served": [load.futures[i].result() for i in pick]}

    # The batches the window counted, and the residues they answered:
    # a batch is counted with its riders, and riders are answered in the
    # order of their batches, so they are the first to have completed.
    batches = after["batches"] - before["batches"]
    riders = after["batched_rows"] - before["batched_rows"]
    first = sorted(load.done, key=load.done.get)[:riders]
    residues_in_batches = int(sum(lengths_n[i] for i in first if ok[i]))
    # Batches by row class and the positions they really computed: the
    # difference of the same two reads.
    class_counts = {
        int(c): int(k) - int(before["batch_class_counts"].get(c, 0))
        for c, k in after["batch_class_counts"].items()}
    class_counts = {c: k for c, k in sorted(class_counts.items()) if k}
    obs = {
        "program": "_packed_encode_batch",
        "batches": batches,
        "residues_in_batches": residues_in_batches,
        "batch_class_counts": class_counts,
        "batched_positions": int(after["batched_positions"]
                                 - before["batched_positions"]),
        "requests_in_window": int(in_window.sum()),
        "residues_in_window": int(lengths_n[in_window].sum()),
        "latency_s": latency,
        "due_s": due_n,
        "seconds": run.seconds,
        "late_s": np.asarray(load.sent) - due_n[:len(load.sent)],
        "stages": stages,
        # What one batch of each row class needs, from shapes alone,
        # and what it takes to compile that class's executable again
        # for its scope map (after the window, like `program_scopes`).
        "classes": {
            cls: {"flops": flops.forward_flops(m, cls, seq_len, segments,
                                               heads=False),
                  "min_bytes": flops.embed_min_bytes(m, cls, seq_len,
                                                     segments)}
            for cls in ladder_rows},
        "class_program": lambda cls: cell_program(wl, run.config, rows=cls),
    }
    _print_pace(np.sort(done_at[in_window]), load, due_n, run.seconds)
    _print_window(n, obs, run)
    return {
        "e2e": e2e,
        "attempted": n,
        "failed": failed,
        "checks": [],
        "memory_peak_bytes": int(memory_peak),
        "obs": obs,
    }, sample


def _print_window(attempted, obs, run):
    """The lines a reader's number can be checked against by hand: what
    was left when the window closed (a backlog that grows is past the
    knee), the batches by row class as `Server.stats()` counted them
    with the real fill, and, traced, the same classes as the trace has
    them (they may differ by the window's edges only) with each one's
    mean device time."""
    counts, positions = obs["batch_class_counts"], obs["batched_positions"]
    fill = 100.0 * obs["residues_in_batches"] / positions if positions else 0.0
    print(f"window: {attempted} requests due, {obs['requests_in_window']} "
          f"answered inside it, {attempted - obs['requests_in_window']} left "
          f"at close; batches by row class (Server.stats) {counts}, "
          f"{obs['residues_in_batches']} residues in {positions} positions "
          f"= fill {fill:.2f} %")
    growth = readers.backlog_growth_per_s(obs["latency_s"], obs["due_s"],
                                          obs["seconds"])
    print(f"backlog: growing by {growth:.1f} requests/s (median of the "
          f"window's last quarter less the third's, over a quarter)")
    per_interval = readers.interval_p95s_ms(obs["latency_s"], obs["due_s"],
                                            obs["seconds"])
    if len(per_interval):
        print(f"p95 of each whole {readers.INTERVAL_S} s, ms: "
              + " ".join(f"{v:.0f}" for v in per_interval)
              + f"; median {np.median(per_interval):.2f}, whole window "
              f"{readers.latency_p95_ms(obs):.2f}")
    if run.trace_summary is None:
        return
    got = span_readers.class_runs(dict(obs, trace=run.trace_summary))
    if got is None:
        print("classes in the trace: not read (no device plane, no `cls=` on the launches, "
              "or the join of runs to launches is not sound)")
        return
    by_class = {}
    for cls, seconds in got:
        by_class.setdefault(cls, []).append(seconds)
    told = sum(len(t) for cls, t in by_class.items() if cls is not None)
    print(f"classes in the trace: {told} of {len(got)} runs classified; "
          + "; ".join(
              f"{cls if cls is not None else 'unknown'} rows x {len(t)} runs, "
              f"mean {1e3 * float(np.mean(t)):.3f} ms"
              for cls, t in sorted(by_class.items(), key=lambda kv: kv[0] or 0)))


def _print_pace(answered, load, due, seconds):
    """One line a far-off run can be read by: how evenly the answers
    came, and how late the generator ran at its worst. A server that
    stalls shows as one long gap between answers; a host that stalls
    shows in the generator too."""
    sent = np.asarray(load.sent)
    late = sent - due[:len(sent)]
    if len(answered) < 2 or len(late) == 0:
        return
    edges = np.concatenate([[0.0], answered, [seconds]])
    gaps = np.diff(edges)
    worst = int(np.argmax(gaps))
    print(f"pace: {len(answered)} answers in the window, first at "
          f"{answered[0]:.3f} s, longest gap {gaps[worst]:.3f} s ending at "
          f"{edges[worst + 1]:.3f} s; generator latest {late.max() * 1e3:.1f} ms "
          f"at {sent[int(np.argmax(late))]:.3f} s")


def _aborted(future) -> bool:
    """Work still queued when the window closed is not a failure of the
    server: the driver aborts it."""
    from proteinbert_tpu.serve.errors import ServerClosedError

    return isinstance(future.exception(), ServerClosedError)


def cell_program(workload: dict, config: dict, rows=None):
    """(jitted function, abstract arguments, static keyword arguments) of
    the program the window times at its largest row class, or at `rows`:
    for `benchmark.rehearse` and for the scope map of each class."""
    import jax
    import jax.numpy as jnp

    from proteinbert_tpu import inference
    from proteinbert_tpu.models import proteinbert

    cfg = program_config(config, workload["overrides"])
    rows = rows or workload["server"]["max_batch"]
    segments = workload["server"]["pack_max_segments"]
    params = jax.eval_shape(
        lambda k: proteinbert.init(k, cfg.model), jax.random.PRNGKey(0))
    grid = jax.ShapeDtypeStruct((rows, cfg.data.seq_len), jnp.int32)
    ann = jax.ShapeDtypeStruct(
        (rows, segments, config["num_annotations"]), jnp.float32)
    return (inference._packed_encode_batch, (params, grid, grid, ann),
            {"cfg": cfg.model})
