"""Word2Vec on a toy corpus (ref analog: dl4j-examples Word2VecRawTextExample).

The SGNS hot loop — the reference's native sg/cbow op (SURVEY D15/N3) —
runs as one fused batched jax program per epoch chunk."""
from deeplearning4j_tpu.nlp.sentence import CollectionSentenceIterator
from deeplearning4j_tpu.nlp.word2vec import Word2Vec

CORPUS = [
    "the king rules the kingdom",
    "the queen rules the kingdom",
    "the king and the queen sit on thrones",
    "dogs chase cats in the garden",
    "cats chase mice in the garden",
    "dogs and cats are animals",
    "mice fear cats and cats fear dogs",
    "the kingdom has a garden",
] * 24


def main():
    w2v = Word2Vec(layer_size=24, window_size=2, epochs=6, negative=5,
                   seed=11, min_word_frequency=2,
                   iterator=CollectionSentenceIterator(CORPUS))
    w2v.fit()
    print("vocab:", w2v.vocab.num_words())
    for a, b in (("king", "queen"), ("dogs", "cats"), ("king", "garden")):
        print(f"similarity({a}, {b}) = {w2v.similarity(a, b):.3f}")
    print("nearest(cats):", w2v.wordsNearest("cats", 3))


if __name__ == "__main__":
    main()
