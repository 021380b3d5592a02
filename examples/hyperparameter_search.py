"""Arbiter hyperparameter search + early-stopped retraining of the winner.

The analog of arbiter-examples' BasicHyperparameterOptimizationExample
(ref: org.deeplearning4j.arbiter MultiLayerSpace + RandomSearchGenerator
+ LocalOptimizationRunner): declare a search space over learning rate and
hidden width, random-search it, then retrain the best candidate under an
early-stopping trainer.

Run: python examples/hyperparameter_search.py [--candidates N]
"""
import argparse

import numpy as np  # noqa: E402


def toy_iter(seed: int):
    """Two separable gaussian classes as a one-DataSet list (arbiter and
    the early-stopping trainer both accept plain DataSet lists)."""
    from deeplearning4j_tpu.data.dataset import DataSet

    rng = np.random.default_rng(seed)
    n = 128
    x0 = rng.normal((-1.0, -1.0, 0.0, 0.5), 0.6, (n // 2, 4))
    x1 = rng.normal((1.0, 1.0, 0.5, -0.5), 0.6, (n // 2, 4))
    x = np.concatenate([x0, x1]).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[[0] * (n // 2) + [1] * (n // 2)]
    perm = rng.permutation(n)
    return [DataSet(x[perm], y[perm])]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--candidates", type=int, default=6)
    args = ap.parse_args()

    from deeplearning4j_tpu.arbiter import (
        ContinuousParameterSpace, DataSetLossScoreFunction,
        IntegerParameterSpace, LocalOptimizationRunner,
        MaxCandidatesCondition, OptimizationConfiguration,
        RandomSearchGenerator)
    from deeplearning4j_tpu.arbiter.space import (
        DenseLayerSpace, MultiLayerSpace, OutputLayerSpace)
    from deeplearning4j_tpu.nn.conf.inputs import InputType

    space = (MultiLayerSpace.Builder()
             .seed(7)
             .updater(ContinuousParameterSpace(1e-3, 1e-1, log_scale=True))
             .add_layer(DenseLayerSpace(n_in=4,
                                        n_out=IntegerParameterSpace(4, 32),
                                        activation="relu"))
             .add_layer(OutputLayerSpace(n_out=2, activation="softmax",
                                         loss_function="mcxent"))
             .set_input_type(InputType.feed_forward(4))
             .build())

    conf = OptimizationConfiguration(
        candidate_generator=RandomSearchGenerator(space, seed=11),
        score_function=DataSetLossScoreFunction(),
        termination_conditions=[MaxCandidatesCondition(args.candidates)],
        train_data=toy_iter(0), test_data=toy_iter(1), epochs=25)
    runner = LocalOptimizationRunner(conf)
    best = runner.execute()
    for r in runner.results:
        print(f"  candidate {r.index}: val loss {r.score:.4f}")
    print(f"best candidate: #{best.index} (val loss {best.score:.4f})")

    # retrain the winning config under early stopping
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.optim.earlystopping import (
        DataSetLossCalculator, EarlyStoppingConfiguration,
        EarlyStoppingTrainer, InMemoryModelSaver,
        MaxEpochsTerminationCondition,
        ScoreImprovementEpochTerminationCondition)

    net = MultiLayerNetwork(best.conf).init()
    es = (EarlyStoppingConfiguration.Builder()
          .score_calculator(DataSetLossCalculator(toy_iter(1)))
          .epoch_termination_conditions(
              MaxEpochsTerminationCondition(60),
              ScoreImprovementEpochTerminationCondition(5, 1e-4))
          .model_saver(InMemoryModelSaver())
          .build())
    res = EarlyStoppingTrainer(es, net, toy_iter(0)).fit()
    print(f"early stopping: best epoch {res.best_model_epoch}, "
          f"val score {res.best_model_score:.4f} "
          f"({res.termination_reason} after {res.total_epochs} epochs)")
    assert res.best_model is not None and np.isfinite(res.best_model_score)
    print("hyperparameter search example PASS")


if __name__ == "__main__":
    main()
