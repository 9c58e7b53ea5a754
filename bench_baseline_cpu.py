"""Measure the reference-class CPU baselines bench.py compares against.

The reference stack (DL4J 0.4 on nd4j-native CPU BLAS) publishes no numbers;
torch-CPU implementations of the same three benchmark configs stand in as the reference-class CPU measurement.  Run this script in the
image to (re)produce ``baseline_cpu.json`` — bench.py reads that file, so the
comparison constants are reproducible, not hand-waved:

    python bench_baseline_cpu.py          # writes baseline_cpu.json

Configs mirror BASELINE.json: LeNet-5 b128 MNIST-shape, ResNet-50 b8 224^2,
GravesLSTM-class char-LM (2x200 LSTM, vocab 77) b64 T50.
"""

import json
import time

import numpy as np
import torch
import torch.nn as nn
import torch.nn.functional as F


def _time_steps(step, warmup, iters):
    for _ in range(warmup):
        step()
    t0 = time.perf_counter()
    for _ in range(iters):
        step()
    return (time.perf_counter() - t0) / iters


def lenet_step_ms(batch=128, warmup=2, iters=10):
    model = nn.Sequential(
        nn.Conv2d(1, 20, 5), nn.MaxPool2d(2, 2),
        nn.Conv2d(20, 50, 5), nn.MaxPool2d(2, 2),
        nn.Flatten(), nn.Linear(50 * 4 * 4, 500), nn.ReLU(),
        nn.Linear(500, 10),
    )
    opt = torch.optim.SGD(model.parameters(), lr=0.01, momentum=0.9)
    x = torch.randn(batch, 1, 28, 28)
    y = torch.randint(0, 10, (batch,))

    def step():
        opt.zero_grad()
        F.cross_entropy(model(x), y).backward()
        opt.step()

    return _time_steps(step, warmup, iters) * 1e3


class _Bottleneck(nn.Module):
    def __init__(self, cin, mid, stride):
        super().__init__()
        cout = mid * 4
        self.c1 = nn.Conv2d(cin, mid, 1, stride, bias=False)
        self.b1 = nn.BatchNorm2d(mid)
        self.c2 = nn.Conv2d(mid, mid, 3, 1, 1, bias=False)
        self.b2 = nn.BatchNorm2d(mid)
        self.c3 = nn.Conv2d(mid, cout, 1, bias=False)
        self.b3 = nn.BatchNorm2d(cout)
        self.proj = None
        if stride != 1 or cin != cout:
            self.proj = nn.Sequential(nn.Conv2d(cin, cout, 1, stride, bias=False),
                                      nn.BatchNorm2d(cout))

    def forward(self, x):
        s = self.proj(x) if self.proj is not None else x
        h = F.relu(self.b1(self.c1(x)))
        h = F.relu(self.b2(self.c2(h)))
        return F.relu(self.b3(self.c3(h)) + s)


def _resnet50():
    layers = [nn.Conv2d(3, 64, 7, 2, 3, bias=False), nn.BatchNorm2d(64),
              nn.ReLU(), nn.MaxPool2d(3, 2, 1)]
    cin, mid = 64, 64
    for stage, n in enumerate((3, 4, 6, 3)):
        for i in range(n):
            layers.append(_Bottleneck(cin, mid, 2 if (stage > 0 and i == 0) else 1))
            cin = mid * 4
        mid *= 2
    layers += [nn.AdaptiveAvgPool2d(1), nn.Flatten(), nn.Linear(cin, 1000)]
    return nn.Sequential(*layers)


def resnet50_imgs_per_sec(batch=8, warmup=1, iters=3):
    model = _resnet50()
    opt = torch.optim.SGD(model.parameters(), lr=0.1, momentum=0.9)
    x = torch.randn(batch, 3, 224, 224)
    y = torch.randint(0, 1000, (batch,))

    def step():
        opt.zero_grad()
        F.cross_entropy(model(x), y).backward()
        opt.step()

    return batch / _time_steps(step, warmup, iters)


def lstm_chars_per_sec(batch=64, seq=50, vocab=77, hidden=200, warmup=1, iters=5):
    class CharLM(nn.Module):
        def __init__(self):
            super().__init__()
            self.rnn = nn.LSTM(vocab, hidden, num_layers=2, batch_first=True)
            self.out = nn.Linear(hidden, vocab)

        def forward(self, x):
            h, _ = self.rnn(x)
            return self.out(h)

    model = CharLM()
    opt = torch.optim.RMSprop(model.parameters(), lr=0.1)
    ids = torch.randint(0, vocab, (batch, seq))
    x = F.one_hot(ids, vocab).float()
    y = torch.roll(ids, -1, 1)

    def step():
        opt.zero_grad()
        F.cross_entropy(model(x).reshape(-1, vocab), y.reshape(-1)).backward()
        opt.step()

    return batch * seq / _time_steps(step, warmup, iters)


def main():
    torch.manual_seed(0)
    out = {
        "lenet_step_ms": round(lenet_step_ms(), 3),
        "resnet50_imgs_per_sec": round(resnet50_imgs_per_sec(), 3),
        "lstm_chars_per_sec": round(lstm_chars_per_sec(), 1),
        "meta": {
            "stack": f"torch-{torch.__version__} CPU",
            "threads": torch.get_num_threads(),
            "note": "reference-class CPU stand-in (DL4J publishes no numbers)",
        },
    }
    with open("baseline_cpu.json", "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
