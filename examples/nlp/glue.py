"""GLUE fine-tuning from real task data (reference
examples/nlp/bert/test_glue_hetu_bert.py + glue_processor/glue.py).

Reads the published GLUE TSV layouts (SST-2, MRPC, CoLA, MNLI) through
the framework's WordPiece tokenizer, fine-tunes
``BertForSequenceClassification``, and reports dev accuracy (+F1 for
MRPC).  Weights can start from a HuggingFace BERT checkpoint
(``--hf_weights`` accepts a torch state_dict file saved with
``torch.save``) or fresh initialization.

    python examples/nlp/glue.py --task sst-2 --data_dir <glue/SST-2> \
        --vocab <bert-base-uncased-vocab.txt> [--hf_weights pytorch_model.bin]
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..")))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--task", default="sst-2",
                    choices=["sst-2", "mrpc", "cola", "mnli"])
    ap.add_argument("--data_dir", required=True)
    ap.add_argument("--vocab", required=True, help="vocab file path OR a registered name like bert-base-uncased (resolved locally via hetu_tpu.tokenizers.resolve_vocab)")
    ap.add_argument("--hf_weights", default=None,
                    help="torch state_dict file of a HF BertModel/"
                         "BertForSequenceClassification")
    ap.add_argument("--max_seq_len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--lr", type=float, default=2e-5)
    ap.add_argument("--hidden", type=int, default=768)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import hetu_tpu as ht
    from hetu_tpu import metrics
    from hetu_tpu.datasets import GLUE_PROCESSORS, convert_examples_to_arrays
    from hetu_tpu.models import BertConfig, BertForSequenceClassification
    from hetu_tpu.tokenizers import BertTokenizer

    tok = BertTokenizer.from_pretrained(args.vocab)
    proc = GLUE_PROCESSORS[args.task]()
    labels = proc.labels()
    train = convert_examples_to_arrays(
        proc.train_examples(args.data_dir), labels, tok, args.max_seq_len)
    dev = convert_examples_to_arrays(
        proc.dev_examples(args.data_dir), labels, tok, args.max_seq_len)
    print(f"{args.task}: {len(train)} train / {len(dev)} dev examples")

    B, S = args.batch, args.max_seq_len
    c = BertConfig(vocab_size=len(tok.vocab), hidden_size=args.hidden,
                   num_hidden_layers=args.layers,
                   num_attention_heads=args.heads,
                   intermediate_size=4 * args.hidden, seq_len=S,
                   max_position_embeddings=max(512, S))
    ids = ht.placeholder_op("g_ids", (B, S), dtype=np.int32)
    tt = ht.placeholder_op("g_tok", (B, S), dtype=np.int32)
    am = ht.placeholder_op("g_am", (B, S))
    y = ht.placeholder_op("g_y", (B,), dtype=np.int32)
    model = BertForSequenceClassification(c, len(labels), name="glue_bert")
    loss, logits = model.loss(ids, tt, am, y)
    opt = ht.AdamWOptimizer(learning_rate=args.lr, weight_decay=0.01)
    ex = ht.Executor({"train": [loss, opt.minimize(loss)],
                      "eval": [logits]}, seed=args.seed)

    if args.hf_weights:
        import torch
        from hetu_tpu.models.hf_import import load_hf_bert_weights
        sd = torch.load(args.hf_weights, map_location="cpu",
                        weights_only=True)
        # accept either a bare BertModel state_dict or a
        # BertForSequenceClassification one ("bert." prefixed)
        if any(k.startswith("bert.") for k in sd):
            sd = {k[len("bert."):]: v for k, v in sd.items()
                  if k.startswith("bert.")}
        load_hf_bert_weights(ex, model.bert, sd, name="glue_bert")
        print("loaded HF weights")

    def feeds(batch):
        return {ids: batch["input_ids"], tt: batch["token_type_ids"],
                am: batch["attention_mask"], y: batch["label_ids"]}

    def evaluate():
        preds, gold = [], []
        # keep the remainder: pad the last partial batch up to B (one
        # compiled shape) and trim its predictions back
        for batch in dev.batches(B, drop_remainder=False):
            n_real = len(batch["label_ids"])
            if n_real < B:
                batch = {k: np.concatenate(
                    [v, np.repeat(v[:1], B - n_real, axis=0)])
                    for k, v in batch.items()}
            out = ex.run("eval", feed_dict=feeds(batch),
                         convert_to_numpy_ret_vals=True)[0]
            preds.append(np.argmax(out, -1)[:n_real])
            gold.append(batch["label_ids"][:n_real])
        preds, gold = np.concatenate(preds), np.concatenate(gold)
        res = {"accuracy": float((preds == gold).mean())}
        if args.task == "mrpc":
            res["f1"] = metrics.f1_score(preds, gold)
        return res

    step = 0
    res = None
    for epoch in range(args.epochs):
        t0 = time.time()
        run_loss = []
        for batch in train.batches(B, shuffle=True, seed=args.seed + epoch):
            out = ex.run("train", feed_dict=feeds(batch),
                         convert_to_numpy_ret_vals=True)
            run_loss.append(float(out[0]))
            step += 1
        res = evaluate()
        print(f"epoch {epoch}: loss {np.mean(run_loss):.4f} "
              f"dev {res} ({time.time()-t0:.1f}s)")
    if res is None:               # --epochs 0: eval-only
        res = evaluate()
        print(f"eval-only dev {res}")
    return res


if __name__ == "__main__":
    from hetu_tpu.platform import enable_compile_cache
    enable_compile_cache()
    main()
