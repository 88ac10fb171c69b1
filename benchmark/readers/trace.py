"""Readers of the reduced profiler trace (harness/tracered.summarize):
device time per XLA module, per op class, exposed collectives, and the
utilization and roofline shares worked out from them. Each returns None
when the trace lacks what it reads (no such module, no such class)."""
from benchmark.harness import loader, roofline, tracered


def _mean(values):
    values = list(values)
    return sum(values) / len(values) if values else None


def _module(run, dev, pattern):
    return tracered.module_stats(dev["device"], run.trace["window"], pattern)


def module_ms_per_call(run, pattern):
    """Mean device time of one execution of the modules matching
    `pattern`, over all devices."""
    per_dev = []
    for dev in run.trace["devices"]:
        calls, seconds = _module(run, dev, pattern)
        if calls:
            per_dev.append(1e3 * seconds / calls)
    return _mean(per_dev)


def module_ms_per_unit(run, pattern, per, scale=1.0):
    """Device time of the modules matching `pattern`, in ms per `scale`
    units of the job's traced tally `per` (e.g. per 1000 prompt tokens
    prefilled while the trace ran)."""
    units = (run.facts.get("traced") or {}).get(per)
    seconds = _mean(_module(run, d, pattern)[1] for d in run.trace["devices"])
    if not units or not seconds:
        return None
    return 1e3 * seconds / (units / scale)


def busy_ms_per_call(run, pattern):
    """Device busy time per execution of the modules matching `pattern`:
    what one step costs the device, whatever ran between the modules."""
    per_dev = []
    for dev in run.trace["devices"]:
        calls, _ = _module(run, dev, pattern)
        if calls:
            per_dev.append(1e3 * dev["busy_s"] / calls)
    return _mean(per_dev)


def mfu(run, pattern):
    """Required flops of one step on one chip over the device busy time
    of a step, as a share of the chip's bf16 peak (%). Recomputation does
    not count: the flops are the configuration's flops_per_item."""
    step_ms = busy_ms_per_call(run, pattern)
    if not step_ms or run.peaks is None:
        return None
    flops = (float(run.cell.config["flops_per_item"])
             * int(run.cell.traffic["batch_per_chip"]))
    return 100.0 * flops / (step_ms / 1e3) / run.peaks["bf16_flops"]


def class_share(run, cls):
    """Share (%) of device busy time spent in ops of class `cls`."""
    shares = [100.0 * d["class_s"].get(cls, 0.0) / d["busy_s"]
              for d in run.trace["devices"] if d["busy_s"]]
    if not any(cls in d["class_s"] for d in run.trace["devices"]):
        return None
    return _mean(shares)


def exposed_share(run, cls):
    """Share (%) of the traced window in which an op of class `cls` ran
    and nothing else did, on the device where that is worst."""
    if not any(cls in d["class_s"] for d in run.trace["devices"]):
        return None
    worst = max(tracered.exposed_seconds(d["device"], run.trace["window"],
                                         run.trace["classify"], cls)
                for d in run.trace["devices"])
    return 100.0 * worst / run.trace["window_s"]


def kernel_roofline(run, cls, cost):
    """Least time the chip could take for the calls of kernel class `cls`
    made while the trace ran — benchmark/kernel_costs/<cost> on the job's
    facts, against the published peaks — over the time the kernel took
    (%)."""
    seconds = _mean(d["class_s"].get(cls, 0.0) for d in run.trace["devices"])
    needed = loader.load_callable("kernel_costs", cost)(run.facts)
    if not needed or not seconds or run.peaks is None:
        return None
    least, _ = roofline.seconds(*needed, run.peaks)
    return 100.0 * least / seconds
