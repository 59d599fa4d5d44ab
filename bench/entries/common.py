"""What the entries share: the program's model configuration built from
a configuration file, and the query's workloads traced by the program."""
from __future__ import annotations


def arch_config(config: dict):
    """``repro.configs.base.ArchConfig`` from the file's ``arch`` block."""
    from repro.configs.base import ArchConfig, SSMConfig
    arch = dict(config["arch"])
    if arch.get("ssm") is not None:
        arch["ssm"] = SSMConfig(**arch["ssm"])
    return ArchConfig(**arch)


def workloads(arch, q: dict) -> list:
    """``opgen.arch_workload`` of each of the query's deployment shapes."""
    from repro.configs.base import ShapeConfig
    from repro.core.opgen import arch_workload
    return [arch_workload(arch, ShapeConfig(w["shape"], w["seq_len"],
                                            w["global_batch"], w["kind"]),
                          n_chips=w["n_chips"], tp=w["tp"])
            for w in q["workloads"]]
