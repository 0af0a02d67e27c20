"""Worker entrypoint for the two-process jax.distributed smoke test.

Run as: python _multihost_worker.py <process_id> <num_processes> <port>

Each process joins the distributed runtime over localhost, agrees on a seed
(host 0 decides), crosses a barrier, then runs a REAL tournament selection on
a replicated population with replicated fitness — printing the decisions so
the parent test can assert both processes made identical ones. This is the
deterministic-replicated-evolution story that replaces the reference's rank-0
+ broadcast_object_list (hpo/tournament.py:161).
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_cpu_collectives_implementation", "gloo")


def main() -> None:
    pid, nproc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]

    from agilerl_tpu.parallel.multihost import (
        barrier,
        broadcast_seed,
        init_multihost,
    )

    init_multihost(f"127.0.0.1:{port}", nproc, pid)
    assert jax.process_count() == nproc, (
        f"expected {nproc} processes, got {jax.process_count()}"
    )

    # host 0 decides 1234; host 1 proposes a different seed and must lose
    seed = broadcast_seed(1234 if pid == 0 else 999)
    print(f"SEED {seed}", flush=True)
    barrier("after-seed")

    import gymnasium as gym
    import numpy as np

    from agilerl_tpu.hpo.tournament import TournamentSelection
    from agilerl_tpu.utils.utils import create_population

    pop = create_population(
        "DQN",
        gym.spaces.Box(low=-1, high=1, shape=(4,)),
        gym.spaces.Discrete(2),
        population_size=4,
        net_config={"latent_dim": 8, "encoder_config": {"hidden_size": (16,)}},
        seed=seed,
    )
    fitness = [3.0, 1.0, 4.0, 1.5]  # replicated, like all-gathered eval scores
    for agent, f in zip(pop, fitness):
        agent.fitness = [f]

    tournament = TournamentSelection(
        tournament_size=2, elitism=True, population_size=4, eval_loop=1,
        rng=np.random.default_rng(seed),
    )
    elite, new_pop = tournament.select(pop)
    print(f"ELITE {elite.index}", flush=True)
    print(f"POP {' '.join(str(a.index) for a in new_pop)}", flush=True)

    # cross-host metric mean: host 0 reports 1.0, host 1 reports 3.0 -> 2.0
    from agilerl_tpu.utils.utils import aggregate_metrics_across_hosts

    agg = aggregate_metrics_across_hosts(1.0 + 2.0 * pid)
    print(f"AGG {agg}", flush=True)
    barrier("done")
    print("DONE", flush=True)


if __name__ == "__main__":
    main()
