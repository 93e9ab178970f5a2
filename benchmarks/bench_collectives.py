"""Mesh-collective sweep over the NoC collective subsystem (simulated:
mesh size x collective x algorithm x router semantics x E PEs/router).

The psum modes on real chips are compared by ``chip_smoke.py --chips 4``.

Run:  PYTHONPATH=src python benchmarks/bench_collectives.py
"""


def mesh_sweep(mesh_sizes=(4, 8), e_pes=(1, 4),
               payload_bits_per_pe=1024) -> list[str]:
    """Simulated-mesh collective sweep: one row per (n, collective,
    algorithm, semantics, E) with latency cycles and network energy (pJ).
    ``E`` PEs per router scale the per-node payload, as in the paper's
    Figs. 7-9 sweep."""
    import dataclasses

    from repro.core.noc import NocConfig
    from repro.core.noc.collective import collective_cost, full_mesh

    variants = [
        ("reduce", "-", "ina"),
        ("reduce", "-", "eject_inject"),
        ("broadcast", "-", "ina"),
        ("broadcast", "-", "eject_inject"),
        ("gather", "-", "ina"),
        ("gather", "-", "eject_inject"),
        ("allreduce", "reduce_bcast", "ina"),
        ("allreduce", "reduce_bcast", "eject_inject"),
        ("allreduce", "rs_ag", "ina"),
        ("allreduce", "rs_ag", "eject_inject"),
    ]
    rows = ["mesh_collective,n,op,algorithm,semantics,e_pes,"
            "latency_cycles,energy_pj,packets"]
    for n in mesh_sizes:
        cfg = dataclasses.replace(NocConfig(), n=n)
        parts = full_mesh(n)
        for e in e_pes:
            payload = payload_bits_per_pe * e
            for op, algo, sem in variants:
                c = collective_cost(op, payload, cfg, participants=parts,
                                    algorithm=algo if algo != "-"
                                    else "reduce_bcast", semantics=sem)
                rows.append(
                    f"mesh_collective,{n},{op},{algo},{sem},{e},"
                    f"{c.latency_cycles},{c.energy_pj:.1f},{c.packets}")
    return rows


if __name__ == "__main__":
    print("\n".join(mesh_sweep()))
