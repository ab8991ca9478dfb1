"""Messages exchanged per job: `BSPStats.total_messages`, mean over the
window's jobs. Engine mixes only."""


def read(obs):
    if obs["kind"] != "engine":
        return None
    records = obs["records"]
    return sum(r.stats.total_messages for r in records) / len(records)
