"""wire_gib_per_step: GiB one replica group hands to the wire per step: the
nbytes of the gradient shards ft_allreduce_sharded stages (a count, from the
arrays' shapes; the worst group)."""


def read(obs):
    values = [g["wire_bytes_per_step"] for g in obs.get("groups", []) if g.get("wire_bytes_per_step")]
    return max(values) / 2**30 if values else None
