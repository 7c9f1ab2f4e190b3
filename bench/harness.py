"""One run of one cell: build the served stack, warm it up, drive the
traffic for the window, check what it served, and reduce the numbers.

The model's family (``bench/families/``, named by the configuration)
gives the program's config, the block columns, the required FLOPs and the
reference; nothing here knows one kind of model from another.

The stack is the program's own, built by its public constructors:
``ServingEngine`` over ``CacheHierarchy`` over ``ShardedKVBlockStore``,
with ``RealModel`` as the engine's ``real_prefill`` hook and
``RuntimeServices`` for the I/O executor, write-behind and maintenance.
The benchmark wraps two of the calls, in its own code: the hook (to time
the copy of blocks to the host and keep sampled blocks) and the
hierarchy's ``fetch``/``fulfill``/``commit`` (spans, and the blocks it
serves for sampled requests).

TTFT runs from a request's due time to the return of the
``ServingEngine.step()`` whose records hold it.  Prompt tokens per second
are those of the requests due in the window, over the time from its start
until the last of them was served.
Set-up is process start to the window's first due request.
"""

from __future__ import annotations

import dataclasses
import gc
import random
import shutil
import tempfile
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.cache.hierarchy import CacheHierarchy
from repro.core.sharded_store import ShardedKVBlockStore
from repro.runtime import RuntimeServices
from repro.serving.compute_model import ComputeModel
from repro.serving.engine import ServingEngine
from repro.serving.real_model import CompileCounter, RealModel, enable_compile_cache

from . import check, generator, spec, stats, trace

GRACE_S = 60.0  # how long requests due in the window are waited for
SAMPLE = 7  # window requests compared besides one of the longest
DECODE_TOKENS = 8  # greedy tokens of the decode check

# the CPU rehearsal: the family cuts every width to a smoke size, and token
# counts are cut by 16
REHEARSAL_TOKEN_SCALE = 16


def log(msg: str) -> None:
    print(msg, flush=True)


def prompt_key(tokens) -> int:
    return hash(tuple(tokens))


def rehearsal_config(cfg: Dict) -> Dict:
    """The configuration at its family's smoke widths, with the memory
    tiers cut as the token counts are."""
    out = spec.family(cfg).rehearsal_config(cfg)
    dep = dict(cfg["deployment"])
    for k in ("device_blocks", "host_blocks"):
        dep[k] = max(4, dep[k] // REHEARSAL_TOKEN_SCALE)
    out["deployment"] = dep
    return out


# ------------------------------------------------------------ sampling
@dataclass
class Picked:
    tokens: List[int]
    # blocks the hierarchy handed over, from block 0; in a mix that shares
    # nothing, those the store returns after the window, None for each
    # block of the prompt it does not return
    served: List[Optional[np.ndarray]]
    prefill: Optional[List[np.ndarray]] = None  # blocks the hook returned
    reused: int = 0


class Sampler:
    """The window requests whose outputs are compared: the first of the
    longest length, and a reservoir of ``k`` others drawn from the seed."""

    def __init__(self, seed: int, k: int, longest: int):
        self.rng = random.Random(seed)
        self.k = k
        self.longest_len = longest
        self.window: set = set()
        self.longest: Optional[Picked] = None
        self.others: List[Picked] = []
        self._by_key: Dict[int, Picked] = {}
        self._seen = 0

    def picked(self) -> List[Picked]:
        return ([self.longest] if self.longest else []) + self.others

    def on_fulfill(self, tokens, blocks) -> None:
        key = prompt_key(tokens)
        if key not in self.window or key in self._by_key:
            return
        p = Picked(list(tokens), list(blocks))
        if self.longest is None and len(tokens) == self.longest_len:
            self.longest = p
        else:
            self._seen += 1
            if len(self.others) < self.k:
                self.others.append(p)
            else:
                j = self.rng.randrange(self._seen)
                if j >= self.k:
                    return
                del self._by_key[prompt_key(self.others[j].tokens)]
                self.others[j] = p
        self._by_key[key] = p

    def on_prefill(self, tokens, reused: int, blocks) -> None:
        p = self._by_key.get(prompt_key(tokens))
        if p is not None:
            p.prefill, p.reused = list(blocks), reused


class PrefillHook:
    """The engine's ``real_prefill``: the program's model, timed."""

    def __init__(self, model: RealModel, sampler: Sampler):
        self.model = model
        self.sampler = sampler
        self.calls: List[Tuple[float, float, float, int, int]] = []  # t, wall, prefill_s, S, reused

    def __call__(self, tokens, reused):
        t0 = time.perf_counter()
        with TraceAnnotation("bench.prefill_hook"):
            blocks, dt = self.model(tokens, reused)
        self.calls.append((t0, time.perf_counter() - t0, dt, len(tokens), reused))
        self.sampler.on_prefill(tokens, reused, blocks)
        return blocks, dt


class BenchHierarchy(CacheHierarchy):
    """The program's hierarchy with the benchmark's spans around its
    phases, and a record of what ``fulfill`` serves."""

    sampler: Sampler
    fulfills: List[Tuple[float, float, int]]  # t, io_s, disk_tokens

    def fetch(self, plan):
        with TraceAnnotation("bench.fetch"):
            return super().fetch(plan)

    def fulfill(self, plan, fetched=None):
        with TraceAnnotation("bench.fulfill"):
            acq = super().fulfill(plan, fetched)
        self.fulfills.append((time.perf_counter(), acq.io_s, acq.disk_tokens))
        self.sampler.on_fulfill(plan.tokens, [n.data for n in acq.nodes])
        return acq

    def commit(self, tokens, new_blocks, acq):
        with TraceAnnotation("bench.commit"):
            return super().commit(tokens, new_blocks, acq)


# -------------------------------------------------------------- window
@dataclass
class Window:
    """What the per-layer readers read (``bench/metrics/<name>.py``)."""

    seconds: float
    start: float
    end: float
    records: List  # RequestRecord of every request due in the window and served
    ttft: List[float]  # the client's TTFT of every request due in the window
    counters_before: Dict[str, float]
    counters_after: Dict[str, float]
    hook_calls: List[Tuple[float, float, float, int, int]]
    fulfills: List[Tuple[float, float, int]]
    required_flops: float  # of the hook calls inside the window
    peaks: Dict
    # the family's (flops, bytes) of each named scope, over the same calls;
    # the trace's ``scope_s`` holds their device time
    scope_costs: Dict[str, Tuple[float, float]] = field(default_factory=dict)
    reduction: Optional[trace.Reduction] = None

    def delta(self, name: str) -> float:
        return self.counters_after.get(name, 0.0) - self.counters_before.get(name, 0.0)


def counters(eng: ServingEngine, store) -> Dict[str, float]:
    """Every counter the program exposes: its metrics registry's gauges
    and the store's stats (``store_<field>``)."""
    out = dict(eng.registry.snapshot()["gauges"])
    for k, v in dataclasses.asdict(store.stats).items():
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            out[f"store_{k}"] = float(v)
    return out


@dataclass
class Served:
    due: Dict[int, float] = field(default_factory=dict)  # rid -> due time
    sent: Dict[int, float] = field(default_factory=dict)
    done: Dict[int, Tuple[object, float]] = field(default_factory=dict)
    prompts: Dict[int, generator.Prompt] = field(default_factory=dict)
    window: List[int] = field(default_factory=list)  # rids due in the window


def drive(eng, traffic, rate, t_lead, t_win, seconds, sampler, on_start, on_end) -> Served:
    """Offer the traffic from ``t_lead``; the window is ``[t_win, t_win +
    seconds)``.  Returns when every request due in the window is served,
    or ``GRACE_S`` after the window."""
    t_end = t_win + seconds
    sv = Served()
    started = ended = False
    outstanding = 0
    sched = [(t_lead + o, False) for o in traffic.arrivals(rate, t_win - t_lead)]
    sched += [(t_win + o, True) for o in traffic.arrivals(rate, seconds)]
    queue = [(due, traffic.next(), w) for due, w in sched]
    i = 0

    def submit(p, due, now, in_window):
        nonlocal outstanding
        sv.due[p.rid], sv.sent[p.rid], sv.prompts[p.rid] = due, now, p
        if in_window:
            sv.window.append(p.rid)
            sampler.window.add(prompt_key(p.tokens))
        eng.submit(p)
        outstanding += 1

    while True:
        now = time.perf_counter()
        if not started and now >= t_win:
            on_start()
            started = True
        if started and not ended and now >= t_end:
            on_end()
            ended = True
        if ended and (now >= t_end + GRACE_S or all(r in sv.done for r in sv.window)):
            break
        while i < len(queue) and queue[i][0] <= now:
            due, p, w = queue[i]
            submit(p, due, now, w)
            i += 1
        if outstanding:
            with TraceAnnotation("bench.engine_step"):
                recs = eng.step()
            t = time.perf_counter()
            for r in recs:
                sv.done[r.rid] = (r, t)
                outstanding -= 1
            continue
        nxt = [t_win, t_end] + ([queue[i][0]] if i < len(queue) else [])
        nxt = [x for x in nxt if x > now]
        if not nxt:
            break
        with TraceAnnotation("bench.wait_arrival"):
            time.sleep(min(nxt) - now)
    return sv


# ----------------------------------------------------------------- run
@dataclass
class Result:
    attempted: int
    failed: int
    e2e: Dict[str, float]
    window: Window
    numbers: Dict[str, float]
    compared: List[Dict]
    control_compared: List[Dict]  # the control in the program's place; empty unless asked
    device: Dict
    stamps: Dict

    @property
    def correct(self) -> bool:
        return all(c["ok"] for c in self.compared)

    @property
    def control_correct(self) -> Optional[bool]:
        return all(c["ok"] for c in self.control_compared) if self.control_compared else None


def run(cfg: Dict, mix: Dict, limits: Dict, seed: int, seconds: float, rate: Optional[float],
        t_process: float, traced: bool = False, rehearse: bool = False, control: bool = False,
        trace_dir: Optional[str] = None) -> Result:
    """One run.  ``control`` also puts the control's outputs in the
    program's place, beside it: the same check, the same limits."""
    if rehearse:
        cfg = rehearsal_config(cfg)
    fam = spec.family(cfg)
    scale = REHEARSAL_TOKEN_SCALE if rehearse else 1
    dev = jax.devices()[0]
    peaks = {} if rehearse else spec.peaks(dev.device_kind)
    dep = cfg["deployment"]
    B = dep["block_tokens"]
    mcfg = fam.model_config(cfg)
    traffic = generator.Traffic(mix, cfg["vocab_size"], B, seed, scale=scale)
    if not rate:
        raise ValueError("the traffic needs a rate")
    lengths = traffic.lengths()
    log(f"cell: {cfg['name']} ({cfg['num_hidden_layers']} layers, d_model {cfg['hidden_size']}), "
        f"rate {rate} req/s, prompt lengths "
        f"{lengths}, seed {seed}, window {seconds} s, device {dev.platform} {dev.device_kind!r}")
    cache_dir = enable_compile_cache()
    store_dir = tempfile.mkdtemp(prefix="kvbench_store_")
    sampler = Sampler(seed, SAMPLE, max(lengths))
    with CompileCounter() as setup_compiles:
        model = RealModel(mcfg, B, seed=seed)
        for L in lengths:
            model.warmup(L, DECODE_TOKENS if L == max(lengths) else 0)
    log(f"set-up compiles: {setup_compiles.compiles} programs in {setup_compiles.seconds!r} s, "
        f"{setup_compiles.cache_hits} from the persistent cache ({cache_dir})")
    runtime = RuntimeServices(io_threads=dep["io_threads"])
    store = ShardedKVBlockStore(store_dir, n_shards=dep["store_shards"], block_size=B,
                                budget_bytes=dep["store_budget_bytes"],
                                io_executor=runtime.executor)
    h = BenchHierarchy(B, dep["device_blocks"], dep["host_blocks"], store=store)
    h.sampler, h.fulfills = sampler, []
    hook = PrefillHook(model, sampler)
    eng = ServingEngine(h, ComputeModel(mcfg), kv_bytes_per_token=mcfg.kv_bytes_per_token,
                        max_batch_tokens=dep["max_batch_tokens"], real_prefill=hook,
                        runtime=runtime)
    warm = traffic.warmup()
    for p in warm:
        eng.submit(p)
    eng.run()
    eng.drain()
    log(f"session warm-up: {len(warm)} histories served, {store.disk_bytes} B on disk")

    win: Dict = {}
    counter = CompileCounter()
    profiling = traced and trace_dir is not None
    if profiling:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # tracing every Python call would slow the host path
        opts.host_tracer_level = 1  # the benchmark's own spans, not the runtime's every event
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    window_span = TraceAnnotation("bench.window")

    def on_start():
        win["before"] = counters(eng, store)
        counter.__enter__()
        window_span.__enter__()

    def on_end():
        window_span.__exit__(None, None, None)
        counter.__exit__()
        win["after"] = counters(eng, store)
        win["backlog"] = runtime.commits.depth if runtime.commits is not None else 0
        win["disk_bytes"] = store.disk_bytes
        if profiling:
            jax.profiler.stop_trace()

    t_lead = time.perf_counter()
    t_win = t_lead + (min(traffic.lead_in_s, 1.0) if rehearse else traffic.lead_in_s)
    sv = drive(eng, traffic, rate, t_lead, t_win, seconds, sampler, on_start, on_end)
    t_end = t_win + seconds
    setup_s = t_win - t_process

    # ---- end-to-end numbers
    ttft, late, done_tokens, recs = [], [], 0, []
    t_gave_up = time.perf_counter()
    t_served = t_end  # when the last request due in the window was served
    for rid in sv.window:
        late.append(sv.sent[rid] - sv.due[rid])
        if rid in sv.done:
            rec, t = sv.done[rid]
            ttft.append(t - sv.due[rid])
            recs.append(rec)
            done_tokens += rec.prompt_len
            t_served = max(t_served, t)
        else:
            ttft.append(t_gave_up - sv.due[rid])
            t_served = t_gave_up
    failed = sum(1 for rid in sv.window if rid not in sv.done)
    e2e = {
        "ttft_p50_s": stats.percentile(ttft, 50) if ttft else None,
        "ttft_p90_s": stats.percentile(ttft, 90) if ttft else None,
        # all the work due in the window, over all the time it took: from
        # the window's start until its last request was served (or given
        # up on), and never less than the window
        "prompt_tokens_per_s": done_tokens / (t_served - t_win),
        "setup_s": setup_s,
    }
    eng.drain()  # raises a write-behind failure
    mem = dev.memory_stats() or {}
    memory_peak = int(mem.get("peak_bytes_in_use", 0))
    third = max(1, len(ttft) // 3)
    stamps = {
        "requests_due_in_window": len(sv.window),
        # a backlog that grows over the window: requests still unserved at
        # its end, and TTFT late in the window against early in it
        "unserved_at_window_end": sum(1 for rid in sv.window
                                      if rid not in sv.done or sv.done[rid][1] >= t_end),
        "ttft_p50_first_third_s": stats.percentile(ttft[:third], 50) if ttft else None,
        "ttft_p50_last_third_s": stats.percentile(ttft[-third:], 50) if ttft else None,
        "generator_lateness_p50_s": stats.percentile(late, 50) if late else None,
        "generator_lateness_max_s": max(late) if late else None,
        "compiles_in_window": counter.compiles,
        "writebehind_backlog_at_window_end": win.get("backlog"),
        "store_disk_bytes_at_window_end": win.get("disk_bytes"),
        # what the run wrote to the store, set-up included: a disk that a
        # check's many runs share fills with it
        "store_bytes_written": store.stats.payload_bytes_stored,
        "set_up_compiles": setup_compiles.compiles,
    }
    calls = [c for c in hook.calls if t_win <= c[0] < t_end]
    scope_costs: Dict[str, Tuple[float, float]] = {}
    if hasattr(fam, "scope_costs"):
        for c in calls:
            for sc, (f, b) in fam.scope_costs(cfg, c[3], c[4]).items():
                f0, b0 = scope_costs.get(sc, (0.0, 0.0))
                scope_costs[sc] = (f0 + f, b0 + b)
    window = Window(
        seconds=seconds, start=t_win, end=t_end, records=recs, ttft=ttft,
        counters_before=win.get("before", {}), counters_after=win.get("after", {}),
        hook_calls=calls, fulfills=[f for f in h.fulfills if t_win <= f[0] < t_end],
        required_flops=sum(fam.prefill_flops(cfg, c[3], c[4]) for c in calls),
        peaks=peaks, scope_costs=scope_costs)
    stamps["required_flops"] = window.required_flops
    if profiling:
        window.reduction = trace.reduce(trace.extract(trace.find_xplane(trace_dir)),
                                        scopes=sorted(scope_costs))

    # ---- the program's decode of the longest sampled prompt, then free it
    picked = sampler.picked()
    gen_prompt = sampler.longest.tokens if sampler.longest else (picked[0].tokens if picked else None)
    gen_toks, gen_logits = [], None
    if gen_prompt is not None:
        gen_toks, logits = model.generate(gen_prompt, DECODE_TOKENS)
        gen_logits = np.asarray(logits, np.float32).reshape(-1)
    share = None
    if window.counters_before:
        req = window.delta("repro_cache_tokens_requested")
        hit = sum(window.delta(f"repro_cache_tokens_hit_{t}") for t in ("device", "host", "disk"))
        share = hit / req if req else None
    over = sum(1 for r in recs if r.reused_tokens > sv.prompts[r.rid].shared)
    shares = mix.get("min_cached_share") is not None
    if not shares:
        # nothing is served from the cache in a mix that shares nothing: what
        # the KV tier gives back is what the store returns for the sampled
        # prompts once the write-behind has drained.  Every block of every
        # prompt was committed, so each one it does not return reads 1.
        for p in picked:
            n = store.probe(p.tokens)
            got = store.get_batch(p.tokens, n) if n else []
            p.served = list(got) + [None] * (len(p.tokens) // B - len(got))
    runtime.close()
    store.close()
    shutil.rmtree(store_dir, ignore_errors=True)
    del eng, h, hook, model, runtime, store, sv
    gc.collect()

    # ---- the reference, and the control where asked
    numbers: Dict[str, float] = {"unanswered_requests": failed, "over_reused_requests": over}
    if shares:
        numbers["cached_share"] = share
    ctrl: Dict[str, float] = {}
    parts, L = fam.block_parts(cfg), cfg["num_hidden_layers"]
    w = fam.init_weights(cfg, seed)
    t_ref = time.perf_counter()
    pre_err, srv_err, c_pre, c_srv = 0.0, None, 0.0, None
    for p in picked:
        rows, _ = fam.forward(cfg, w, p.tokens)
        first = p.reused // B
        expect = len(p.tokens) // B - first
        got = list(p.prefill or [])[:expect]
        got += [None] * (expect - len(got))
        pre_err = max(pre_err, check.worst_block_error(got, rows, first, parts, L, B))
        if p.served:
            srv_err = max(srv_err or 0.0,
                          check.worst_block_error(p.served, rows, 0, parts, L, B))
        if control:
            crows = fam.forward(cfg, w, p.tokens, fp8=True)[0].astype(np.float16)
            cblocks = [crows[i * B:(i + 1) * B] for i in range(len(p.tokens) // B)]
            c_pre = max(c_pre, check.worst_block_error(cblocks[first:], rows, first, parts, L, B))
            if p.served:
                c_srv = max(c_srv or 0.0, check.worst_block_error(
                    [check.int8_round_trip(b) for b in cblocks[:len(p.served)]], rows, 0,
                    parts, L, B))
    numbers["kv_prefill_rel_err"] = pre_err if picked else None
    if srv_err is not None:
        numbers["kv_served_rel_err"] = srv_err
    if gen_prompt is not None:
        seq = list(gen_prompt) + list(gen_toks)
        _, ref_logits = fam.forward(cfg, w, seq, head_rows=[len(seq) - 1])
        numbers["decode_logits_rel_err"] = check.relative_error(gen_logits, ref_logits)
        if control:
            _, c_logits = fam.forward(cfg, w, seq, head_rows=[len(seq) - 1], fp8=True)
            ctrl["decode_logits_rel_err"] = check.relative_error(c_logits, ref_logits)
    if control:
        ctrl["kv_prefill_rel_err"] = c_pre
        if c_srv is not None:
            ctrl["kv_served_rel_err"] = c_srv
    del w
    gc.collect()
    stamps["reference_s"] = time.perf_counter() - t_ref
    stamps["compared_requests"] = len(picked)
    stamps["compared_blocks"] = sum(len(p.tokens) // B for p in picked)
    stamps["compared_served_blocks"] = sum(b is not None for p in picked for b in p.served)

    lims = dict(limits)
    lims["unanswered_requests"] = {"max": 0}
    lims["over_reused_requests"] = {"max": 0}
    if shares:
        lims["cached_share"] = {"min": mix["min_cached_share"]}
    compared = check.verdict(numbers, lims)
    control_compared = check.verdict(dict(numbers, **ctrl), lims) if control else []
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": memory_peak}
    return Result(len(ttft), failed, e2e, window, numbers, compared, control_compared, device,
                  stamps)
