"""ml_batch_s.lat (M_L tier): median wall seconds of the upper tier's
batches, dispatch to tokens on the host, as the program measures them
(`stats["ml_batch_s"]`, its `ml.batch` spans), over the batches that
hold at least one request due in the window: the audit log's
`large_complete` events join each request to its batch. A program that
reports no batch seconds reads nothing."""
import statistics


def read(run):
    batches = run.stats.get("ml_batch_s") if run.stats else None
    if not batches or run.audit is None:
        return None
    due = {r.rid for r, m in zip(run.reqs, run.measured) if m}
    # stats["ml_batch_s"] is the first edge's backend: tier 1's batches
    ids = {e["batch_id"] for e in run.audit
           if e["event"] == "large_complete" and e.get("tier") == 1
           and e["rid"] in due}
    secs = [b["seconds"] for b in batches if b["batch_id"] in ids]
    return statistics.median(secs) if secs else None
