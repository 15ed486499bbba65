"""Host process: what the host's cores did in the window, from the CPU
stamps on the program's spans and the process's CPU counter. The
arithmetic, once; every reader of it is a line.

A span whose two edges were stamped by one thread carries `thread.id`
and `thread.cpu_ns`, the CPU that thread burned between them. For such
a span S: `wall(S)`, `cpu(S)`, and `off(S) = wall - cpu`, the time its
thread was off a core (asleep, or runnable behind the interpreter lock:
the clock cannot tell which). The spans of one thread nest in time,
whatever trace and parent each hangs under (a launch that the last
submitter of a window flushes hangs under the FIRST member's
`batcher.Search`, and an inline `coalescer.launch` is the sibling of
the `batcher.dispatch` it lies inside), so S's children here are the
stamped spans of its thread that lie directly inside its interval:
`cpu_self(S) = cpu(S) - sum cpu(child)`, `wall_self` likewise, and off-
core self time is `wall_self - cpu_self`. A span of another thread is a
root of that thread and is subtracted from nobody; one without the
attributes (`http.request`, `frontend.queue_wait`, `coalescer.wait`,
`device.scan`: waits that cross threads) enters no sum.

Only sums are read, and off-core time is taken of a SUM (`off_core`),
never span by span: where the kernel accounts CPU by the tick (the v5e
hosts: `thread.cpu_ns` is 0 or a multiple of 10 ms) one span's
difference means nothing, a thousand spans' sum is a profile at 100 Hz.

A program without the attributes or the counter gives every reader
`None`."""
from chipbench.layers.spans import named, searches
from chipbench.lib import metric_sum
from chipbench.xplane import clip, merge

TID, CPU = "thread.id", "thread.cpu_ns"
SEARCH, LAUNCH = "batcher.Search", "coalescer.launch"
PROCESS_CPU = "process_cpu_seconds_total"

# the spans whose code blocks by design: a future another thread
# completes, the device, a put's fence, IO or another search's put, the
# collective lock. Off-core time in any other span is the interpreter
# lock, the kernel's scheduler, or a blocking call nobody spanned
NAMED_WAITS = {"batcher.await_launch", "batcher.sync", "batcher.place",
               "batcher.stage", "dispatch.lock_wait"}

# a span whose thread hands the work to another and sleeps until it is
# done: under the program's dispatch watchdog `coalescer.launch` runs
# the launch on a pool thread, where its `dispatch.<stage>` children
# are stamped. What those cover is taken off the span's own off-core
# time (they account for themselves, on their thread)
HANDS_OFF = {LAUNCH}


def stamped(spans: list) -> list:
    return [s for s in spans if CPU in s["attributes"]]


def children(spans: list) -> dict:
    """span id -> the stamped spans of its thread that lie directly
    inside it, for every stamped span."""
    by_thread: dict = {}
    for s in stamped(spans):
        by_thread.setdefault(s["attributes"][TID], []).append(s)
    kids: dict = {}
    for ss in by_thread.values():
        ss.sort(key=lambda s: (s["start_ns"], -s["end_ns"]))
        open_: list = []
        for s in ss:
            kids[s["span_id"]] = []
            while open_ and open_[-1]["end_ns"] < s["end_ns"]:
                open_.pop()
            if open_:
                kids[open_[-1]["span_id"]].append(s)
            open_.append(s)
    return kids


def _covered(span: dict, others: list) -> int:
    """ns of `span` that the union of `others` covers."""
    return sum(b - a for a, b in merge(clip(
        [[o["start_ns"], o["end_ns"]] for o in others],
        span["start_ns"], span["end_ns"])))


def handed_off(spans: list) -> dict:
    """span id -> its stamped children BY PARENT on another thread, for
    the spans in HANDS_OFF."""
    ids = {s["span_id"]: s for s in stamped(spans) if s["name"] in HANDS_OFF}
    out: dict = {i: [] for i in ids}
    for s in stamped(spans):
        p = ids.get(s["parent_id"])
        if p is not None and s["attributes"][TID] != p["attributes"][TID]:
            out[p["span_id"]].append(s)
    return out


def self_times(spans: list) -> list:
    """[(span, wall_self, cpu_self, away)] in ns, for every stamped
    span: `away` is what of a HANDS_OFF span the work it handed to
    another thread covers. Empty on a program that stamps none."""
    kids, away = children(spans), handed_off(spans)
    out = []
    for s in stamped(spans):
        mine = kids[s["span_id"]]
        out.append((
            s,
            s["end_ns"] - s["start_ns"]
            - sum(c["end_ns"] - c["start_ns"] for c in mine),
            s["attributes"][CPU] - sum(c["attributes"][CPU] for c in mine),
            _covered(s, away.get(s["span_id"], []))))
    return out


def off_core(rows) -> int:
    """ns the threads of these `self_times` rows were off a core in
    their own code: summed first, then held at 0."""
    return max(0, sum(wall - cpu - away for _s, wall, cpu, away in rows))


def process_cpu_s(run: dict):
    """CPU seconds the process burned in the window, every thread of it
    (the benchmark's own included: in a traced run the profiler and the
    read of its trace); None without the counter."""
    c = run["counters"]
    if PROCESS_CPU not in c["after"]:
        return None
    return (metric_sum(c["after"], PROCESS_CPU)
            - metric_sum(c["before"], PROCESS_CPU))


def cores_busy(run: dict):
    """Cores the process kept busy: near 1.0 with searches waiting says
    one interpreter lock is the limit."""
    cpu = process_cpu_s(run)
    return cpu / run["window_wall_s"] if cpu is not None else None


def search_cpu_ms(run: dict):
    """CPU a search costs the host, ms: `cpu_self` summed over every
    stamped span of the window's search traces, over those traces (a
    fused launch sits in one member's trace: only the mean is right)."""
    traces = searches(run["spans"])
    rows = self_times([s for ss in traces.values() for s in ss])
    if not rows:
        return None
    return sum(cpu for _s, _w, cpu, _a in rows) / len(traces) / 1e6


def launch_cpu_ms(run: dict):
    """CPU a launch costs the host, ms: mean `thread.cpu_ns` of
    `coalescer.launch`, with that of its `dispatch.<stage>` children
    where the watchdog stamped them on another thread (on its own
    thread they are inside it)."""
    launches = named(stamped(run["spans"]), LAUNCH)
    if not launches:
        return None
    away = handed_off(run["spans"])
    return sum(s["attributes"][CPU]
               + sum(c["attributes"][CPU] for c in away[s["span_id"]])
               for s in launches) / len(launches) / 1e6


def unnamed_offcore_share(run: dict):
    """% of the searches' time in the batcher in which a thread doing
    their work was off a core in code that has no reason to sleep:
    off-core self time of the stamped spans at or under a
    `batcher.Search` that are not in NAMED_WAITS, over summed
    `wall(batcher.Search)`."""
    spans = run["spans"]
    whole = sum(s["end_ns"] - s["start_ns"]
                for s in named(stamped(spans), SEARCH))
    if not whole:
        return None
    parent = {s["span_id"]: s for s in spans}

    def under_search(s):
        while s is not None:
            if s["name"] == SEARCH:
                return True
            s = parent.get(s["parent_id"])
        return False

    return 100.0 * off_core(
        row for row in self_times(spans)
        if row[0]["name"] not in NAMED_WAITS and under_search(row[0])) / whole


def spanned_cpu_share(run: dict):
    """% of the process's CPU in the window that the spans explain:
    `cpu_self` over all stamped spans, over the counter's delta."""
    cpu, rows = process_cpu_s(run), self_times(run["spans"])
    if not cpu or not rows:
        return None
    return 100.0 * sum(c for _s, _w, c, _a in rows) / (cpu * 1e9)
