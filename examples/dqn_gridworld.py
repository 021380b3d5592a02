"""DQN on a GridWorld MDP (ref analog: RL4J QLearningDiscrete examples).

The Q-network, target sync, and replay sampling all run inside one jitted
train step; the environment loop stays host-side (the reference's
Learning/ExpReplay split maps to host env + device step)."""
from deeplearning4j_tpu.rl.mdp import GridWorld
from deeplearning4j_tpu.rl.qlearning import (QLearningConfiguration,
                                             QLearningDiscreteDense)


def main():
    conf = QLearningConfiguration(seed=7, max_step=2500, batch_size=32,
                                  update_start=100,
                                  target_dqn_update_freq=150,
                                  epsilon_nb_step=1500, learning_rate=2e-3,
                                  double_dqn=True, max_epoch_step=40)
    learner = QLearningDiscreteDense(GridWorld(8), conf, hidden=[32])
    rewards = learner.train()
    policy = learner.get_policy()
    score = policy.play(GridWorld(8), max_steps=20)
    print(f"episodes: {len(rewards)}, greedy-policy reward: {score:.3f}")
    assert score > 0.9


if __name__ == "__main__":
    main()
