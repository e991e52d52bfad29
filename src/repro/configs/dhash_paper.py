"""The paper's own workload as a config: a distributed DHash service
(lookup/insert/delete batches + continuous rebuild) sharded over the
production mesh. This is the arch the dry-run uses to lower the paper's
technique itself at scale."""
from dataclasses import dataclass


@dataclass(frozen=True)
class DHashServiceConfig:
    arch_id: str = "dhash-paper"
    backend: str = "linear"
    capacity_per_shard: int = 1 << 20     # ~1M entries per model shard
    chunk: int = 4096                     # rebuild chunk (hazard buffer)
    lookups_per_step: int = 1 << 16       # per shard
    updates_per_step: int = 1 << 13       # per shard (insert + delete each)
    fwd_hazard: bool = False              # hazard via MIGRATED-slot forwarding


CONFIG = DHashServiceConfig()


def smoke() -> DHashServiceConfig:
    return DHashServiceConfig(capacity_per_shard=4096, chunk=256,
                              lookups_per_step=1024, updates_per_step=256)
