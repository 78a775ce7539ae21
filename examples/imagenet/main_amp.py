"""ImageNet training entry point (reference:
``examples/imagenet/main_amp.py`` — the canonical end-to-end Apex example:
``amp.initialize`` + ``amp.scale_loss`` around a ResNet training loop).

Differences from the reference, by environment design:
* model comes from the local ``resnet.py`` (no torchvision in the image);
* ``--synthetic`` trains on generated data so the smoke path (BASELINE
  config 0: ResNet-50, ``--opt-level O0``, CPU, loss decreases) needs no
  dataset on disk;  with a data dir the standard ImageFolder pipeline is
  used when torchvision is available;
* O2/O3 cast to bfloat16 (TPU-native half) rather than float16.

Run:  python main_amp.py --synthetic -b 8 --iters 20 --opt-level O0
"""
from __future__ import annotations

import argparse
import sys
import time

import torch
import torch.nn as nn

sys.path.insert(0, __file__.rsplit("/", 3)[0])   # repo root on sys.path

from apex_tpu import amp
from examples.imagenet.resnet import resnet18, resnet50


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        description="PyTorch ImageNet training with apex_tpu.amp")
    p.add_argument("data", nargs="?", default=None,
                   help="path to dataset (omit with --synthetic)")
    p.add_argument("--arch", "-a", default="resnet50",
                   choices=["resnet18", "resnet50"])
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("-b", "--batch-size", type=int, default=8)
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--weight-decay", type=float, default=1e-4)
    p.add_argument("--print-freq", type=int, default=10)
    p.add_argument("--opt-level", type=str, default="O0")
    p.add_argument("--loss-scale", type=str, default=None)
    p.add_argument("--keep-batchnorm-fp32", type=str, default=None)
    p.add_argument("--synthetic", action="store_true",
                   help="generated data (no dataset needed)")
    p.add_argument("--iters", type=int, default=None,
                   help="cap steps per epoch (smoke tests)")
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--num-classes", type=int, default=1000)
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args(argv)


def synthetic_loader(args):
    """Deterministic fake-data batches with learnable signal: the label is
    recoverable from the image so the loss can actually decrease."""
    g = torch.Generator().manual_seed(args.seed)
    n_batches = args.iters or 10
    batches = []
    for _ in range(n_batches):
        target = torch.randint(0, args.num_classes, (args.batch_size,),
                               generator=g)
        images = torch.randn(args.batch_size, 3, args.image_size,
                             args.image_size, generator=g) * 0.1
        # plant a class-dependent mean so the task is learnable
        images += (target.float() / args.num_classes
                   ).view(-1, 1, 1, 1)
        batches.append((images, target))
    return batches


def main(argv=None, return_state=False):
    """Train; returns the per-iteration loss trace, plus (with
    ``return_state=True``) the final fp32 parameter vectors — the hooks the
    cross-run comparison tier uses to assert O0/O1/O2/O3 runs track each
    other (reference: ``tests/L1/common/compare.py``)."""
    args = parse_args(argv)
    torch.manual_seed(args.seed)

    model = {"resnet18": resnet18, "resnet50": resnet50}[args.arch](
        num_classes=args.num_classes)
    criterion = nn.CrossEntropyLoss()
    optimizer = torch.optim.SGD(model.parameters(), args.lr,
                                momentum=args.momentum,
                                weight_decay=args.weight_decay)

    loss_scale = args.loss_scale
    if loss_scale is not None and loss_scale != "dynamic":
        loss_scale = float(loss_scale)
    keep_bn = args.keep_batchnorm_fp32
    if isinstance(keep_bn, str):
        keep_bn = {"True": True, "False": False}.get(keep_bn, None)

    model, optimizer = amp.initialize(
        model, optimizer, opt_level=args.opt_level,
        keep_batchnorm_fp32=keep_bn, loss_scale=loss_scale)

    if args.synthetic or args.data is None:
        loader = synthetic_loader(args)
    else:  # pragma: no cover - needs torchvision + dataset on disk
        import torchvision.datasets as datasets
        import torchvision.transforms as transforms
        ds = datasets.ImageFolder(
            args.data,
            transforms.Compose([
                transforms.RandomResizedCrop(args.image_size),
                transforms.ToTensor(),
            ]))
        loader = torch.utils.data.DataLoader(
            ds, batch_size=args.batch_size, shuffle=True)

    losses = []
    model.train()
    for epoch in range(args.epochs):
        t0 = time.time()
        for i, (images, target) in enumerate(loader):
            if args.iters is not None and i >= args.iters:
                break
            output = model(images)
            loss = criterion(output.float(), target)
            optimizer.zero_grad()
            with amp.scale_loss(loss, optimizer) as scaled_loss:
                scaled_loss.backward()
            optimizer.step()
            losses.append(loss.item())
            if i % args.print_freq == 0:
                print(f"Epoch {epoch} [{i}] loss {loss.item():.4f} "
                      f"({(i + 1) / (time.time() - t0):.2f} it/s)")
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f})")
    if return_state:
        state = [p.detach().float().cpu().numpy()
                 for p in model.parameters()]
        return losses, state
    return losses


if __name__ == "__main__":
    from apex_tpu.utils.compile_cache import \
        enable_persistent_compile_cache
    enable_persistent_compile_cache()
    main()
