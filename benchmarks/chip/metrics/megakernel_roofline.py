"""``megakernel_roofline``: the least time the chips could take for the
window's points (``work/megakernel.py`` counts, ``peaks.json``), over the
megakernel's device time.  Prints the bound that sets the least time."""
import importlib.util
import os

_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{name.replace('/', '_')}",
        os.path.join(_HERE, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read(run):
    tr, rec = run["trace"], run["record"]
    if tr is None or "sweeps" not in rec or not tr["kernel_s"]:
        return None
    n_dev = max(tr["n_devices"], 1)
    chunks = sum(-(-s["n_points"] // (1 << 18)) for s in rec["sweeps"])
    roof = _load("work/megakernel").roofline(
        points=rec["points"], chunks=chunks,
        kernel_s_per_device=tr["kernel_s"] / n_dev, n_devices=n_dev,
        algorithms=run["config"]["algorithms"],
        peaks=_load("peaks").peaks(run["device_kind"]))
    print(f"megakernel_roofline: bound {roof['bound']}, least "
          f"{roof['least_s']} s for {roof['flops']} flops and "
          f"{roof['bytes']} bytes a chip", flush=True)
    return roof["share_pct"]
