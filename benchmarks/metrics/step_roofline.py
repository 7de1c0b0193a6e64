"""step_roofline: see step_roofline.json beside this file."""


def bound(run):
    """(least seconds, which peak binds) for one chip's rows of a step."""
    needs = run["config"].step_needs(
        run["cfg"], run["batch_rows"] // run["chips"])
    by_bytes = needs["bytes"] / run["peaks"]["hbm_bytes_per_s"]
    by_flops = needs["flops"] / run["peaks"]["flops_per_s"]
    return max(by_bytes, by_flops), (
        "bytes" if by_bytes >= by_flops else "operations")


def note(run):
    """Which peak binds, for the detail line."""
    seconds, which = bound(run)
    return {"least_step_s": seconds, "bound_by": which}


def read(run):
    tr = run["trace"]
    if not tr or not tr["step_device_ms"] or not run["peaks"]:
        return None
    return 100.0 * bound(run)[0] / (tr["step_device_ms"] / 1e3)
