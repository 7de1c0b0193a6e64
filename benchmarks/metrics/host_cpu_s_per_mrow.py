"""host_cpu_s_per_mrow: see host_cpu_s_per_mrow.json beside this file."""


def read(run):
    return run["cpu_s"] / (run["rows"] / 1e6) if run["rows"] else None
