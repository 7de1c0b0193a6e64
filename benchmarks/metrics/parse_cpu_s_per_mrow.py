"""parse_cpu_s_per_mrow: see parse_cpu_s_per_mrow.json beside this file."""

def read(run):
    pipe = run["pipeline"]
    if "parse_cpu_ns" not in pipe or not run["rows"]:
        return None
    ns = pipe["parse_cpu_ns"] + pipe.get("reader_cpu_ns", 0.0)
    return ns / 1e9 / (run["rows"] / 1e6)


def note(run):
    rows = run["rows"] / 1e6
    return {k: run["pipeline"].get(k, 0.0) / 1e9 / rows
            for k in ("reader_cpu_ns", "parse_cpu_ns")}
