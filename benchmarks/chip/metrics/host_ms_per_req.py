"""Host milliseconds per finished prefill: the self time of the program's
engine and pool spans (`rcllm.engine.*`, `rcllm.pool.*`, without the
`*.run` spans that dispatch and wait on the device), summed over the
window's ticks, over the prefills the ticks finished (`TickRecord.phase_s`,
`.n_finalized`); nothing where the ticks carry no spans."""

HOST = ("rcllm.engine.", "rcllm.pool.")


def read(run):
    if not run.ticks or not hasattr(run.ticks[0], "phase_s"):
        return None
    n = sum(x.n_finalized for x in run.ticks)
    host = sum(s for x in run.ticks for name, s in x.phase_s
               if name.startswith(HOST) and not name.endswith(".run"))
    return 1e3 * host / n if n else None
