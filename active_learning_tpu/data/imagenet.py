"""ImageNet-scale disk-backed datasets.

* ``ImageFolderDataset`` — the reference's torchvision ImageFolder pattern
  (src/data_utils/custom_imagenet.py:9-42): class-per-subdirectory layout,
  JPEG decode at access time.
* ``FileListDataset`` — the ImageNet-LT long-tailed variant
  (src/data_utils/custom_imbalanced_imagenet.py:17-46): a text file of
  ``relative/path label`` lines.

Host transforms (decode-time, data-dependent so they can't live in jit):
RandomResizedCrop(224) for the train view, Resize(256)+CenterCrop(224) for
the al/test views (custom_imagenet.py:45-54).  The horizontal flip and
normalization run on device (data/augment.py).  Decoding is parallelized by
the pipeline's prefetch threads and the native batch-gather component.
"""

from __future__ import annotations

import os
from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..registry import DATASETS
from .core import Dataset, IMAGENET_NORM, ViewSpec

_IMG_EXTS = (".jpg", ".jpeg", ".png", ".bmp", ".webp")


def _require_pil():
    try:
        from PIL import Image  # noqa: F401
        return Image
    except ImportError as e:  # pragma: no cover
        raise ImportError(
            "PIL is required for disk-backed image datasets") from e


def random_resized_crop_params(h: int, w: int, rng: np.random.Generator,
                               scale=(0.08, 1.0), ratio=(3 / 4, 4 / 3)
                               ) -> Tuple[int, int, int, int]:
    """torchvision RandomResizedCrop.get_params semantics: sample area and
    log-uniform aspect ratio, 10 attempts then center-crop fallback."""
    area = h * w
    log_ratio = (np.log(ratio[0]), np.log(ratio[1]))
    for _ in range(10):
        target_area = area * rng.uniform(scale[0], scale[1])
        aspect = np.exp(rng.uniform(log_ratio[0], log_ratio[1]))
        cw = int(round(np.sqrt(target_area * aspect)))
        ch = int(round(np.sqrt(target_area / aspect)))
        if 0 < cw <= w and 0 < ch <= h:
            top = int(rng.integers(0, h - ch + 1))
            left = int(rng.integers(0, w - cw + 1))
            return top, left, ch, cw
    # Fallback: center crop at the closest valid ratio.
    in_ratio = w / h
    if in_ratio < ratio[0]:
        cw, ch = w, int(round(w / ratio[0]))
    elif in_ratio > ratio[1]:
        ch, cw = h, int(round(h * ratio[1]))
    else:
        cw, ch = w, h
    top = (h - ch) // 2
    left = (w - cw) // 2
    return top, left, ch, cw


class _DiskImageDataset(Dataset):
    """Shared decode/transform logic for disk-backed datasets.

    Two decode paths, same transform semantics:
      * native (default): batch JPEG decode + crop + bilinear resize in the
        C++ component (native/decode.cpp) with its own thread pool — crop
        rectangles are still computed here in Python from the per-(seed,
        epoch, index) RNG, so randomness is identical across paths;
      * PIL fallback: per-image decode, used when the native library is
        unavailable or a file isn't a baseline JPEG.
    """

    def __init__(self, paths: List[str], targets: Sequence[int],
                 num_classes: int, view: ViewSpec, train_transform: bool,
                 image_size: int = 224, resize_size: int = 256,
                 limit: Optional[int] = None, seed: int = 0,
                 use_native: bool = True, decode_threads: int = 4):
        self.paths = paths
        self.targets = np.asarray(targets, dtype=np.int64)
        self.num_classes = num_classes
        self.view = view
        self.train_transform = train_transform
        self.image_size = image_size
        self.resize_size = resize_size
        self._limit = limit
        self._seed = seed
        self._epoch = 0
        self._use_native = use_native and os.environ.get(
            "AL_TPU_NO_NATIVE") != "1"
        self.decode_threads = decode_threads
        # (height, width) per index, filled on first native touch — image
        # files are immutable, so headers are parsed at most once.
        self._dims_cache: dict = {}
        self.image_shape = (image_size, image_size, 3)

    def __len__(self) -> int:
        if self._limit is not None:
            return min(self._limit, len(self.paths))
        return len(self.paths)

    def set_epoch(self, epoch: int) -> None:
        """Advance the crop-RNG stream: crops are a pure function of
        (seed, epoch, index) — reproducible regardless of gather order or
        decode-thread interleaving (torch draws crop params from a shared
        global stream, so its crops depend on worker scheduling)."""
        self._epoch = int(epoch)

    def _decode_one(self, path: str, index: int) -> np.ndarray:
        PILImage = _require_pil()
        with open(path, "rb") as fh:
            img = PILImage.open(fh).convert("RGB")
        s = self.image_size
        if self.train_transform:
            rng = np.random.default_rng(
                (self._seed, self._epoch, int(index)))
            top, left, ch, cw = random_resized_crop_params(
                img.height, img.width, rng)
            img = img.resize((s, s), PILImage.BILINEAR,
                             box=(left, top, left + cw, top + ch))
        else:
            # Resize(256) (short side) + CenterCrop(224).
            r = self.resize_size
            if img.width <= img.height:
                new_w, new_h = r, max(1, int(round(img.height * r / img.width)))
            else:
                new_h, new_w = r, max(1, int(round(img.width * r / img.height)))
            img = img.resize((new_w, new_h), PILImage.BILINEAR)
            left = (new_w - s) // 2
            top = (new_h - s) // 2
            img = img.crop((left, top, left + s, top + s))
        return np.asarray(img, dtype=np.uint8)

    def _crop_rect(self, h: int, w: int, index: int
                   ) -> Tuple[int, int, int, int]:
        """(top, left, ch, cw) for one image under the current view."""
        if self.train_transform:
            rng = np.random.default_rng(
                (self._seed, self._epoch, int(index)))
            return random_resized_crop_params(h, w, rng)
        # Resize(short=256) + CenterCrop(224) == centered crop of
        # 224 * short/256 in the original image, bilinear-resized.
        short = min(h, w)
        box = int(round(self.image_size * short / self.resize_size))
        return (h - box) // 2, (w - box) // 2, box, box

    @property
    def decoder(self) -> str:
        """The decode path gathers take: "native" (native/decode.cpp,
        built from this checkout on first use) or "pil"."""
        from . import native
        return ("native" if self._use_native and native.load() is not None
                else "pil")

    def _native_dims(self, idxs: np.ndarray) -> Optional[np.ndarray]:
        """Per-index (h, w) via the header cache; -1 rows mean libjpeg
        can't handle that file (PIL decodes it instead)."""
        from . import native
        missing = [int(i) for i in idxs if int(i) not in self._dims_cache]
        if missing:
            dims = native.jpeg_dims([self.paths[i] for i in missing],
                                    self.decode_threads)
            if dims is None:
                return None
            for i, hw in zip(missing, dims):
                self._dims_cache[i] = (int(hw[0]), int(hw[1]))
        return np.asarray([self._dims_cache[int(i)] for i in idxs],
                          dtype=np.int32)

    def _gather_native(self, idxs: np.ndarray) -> Optional[np.ndarray]:
        """Batch decode via native/decode.cpp.  Files the native path can't
        handle (non-JPEG extension, CMYK encodings, parse failures) fall
        back to PIL INDIVIDUALLY — one odd file never disables the fast
        path for the rest of the dataset."""
        from . import native
        if native.load() is None:
            self._use_native = False  # no library: skip the probe forever
            return None
        paths = [self.paths[int(i)] for i in idxs]
        is_jpeg = np.asarray(
            [p.lower().endswith((".jpg", ".jpeg")) for p in paths])
        dims = self._native_dims(idxs) if is_jpeg.any() else None
        if dims is None:
            return None
        ok = is_jpeg & (dims[:, 0] > 0)
        out = np.empty((len(idxs), *self.image_shape), dtype=np.uint8)
        if ok.any():
            sel = np.flatnonzero(ok)
            rects = np.asarray(
                [self._crop_rect(*self._dims_cache[int(idxs[i])],
                                 int(idxs[i])) for i in sel],
                dtype=np.int32)
            decoded, failed = native.decode_crop_resize(
                [paths[i] for i in sel], rects, self.image_size,
                self.decode_threads)
            out[sel] = decoded
            ok[sel[failed]] = False
        for i in np.flatnonzero(~ok):
            out[i] = self._decode_one(paths[i], int(idxs[i]))
        return out

    def gather(self, idxs: np.ndarray) -> np.ndarray:
        idxs = np.asarray(idxs)
        if self._use_native:
            out = self._gather_native(idxs)
            if out is not None:
                return out
        out = np.empty((len(idxs), *self.image_shape), dtype=np.uint8)
        for i, idx in enumerate(idxs):
            out[i] = self._decode_one(self.paths[int(idx)], int(idx))
        return out


class ImageFolderDataset(_DiskImageDataset):
    """Class-per-subdirectory layout (torchvision ImageFolder semantics:
    classes are the sorted subdirectory names)."""

    def __init__(self, root: str, view: ViewSpec, train_transform: bool,
                 num_classes: int = 1000, limit: Optional[int] = None,
                 seed: int = 0):
        classes = sorted(
            d for d in os.listdir(root)
            if os.path.isdir(os.path.join(root, d)))
        if not classes:
            raise FileNotFoundError(f"No class directories under '{root}'")
        class_to_idx = {c: i for i, c in enumerate(classes)}
        paths, targets = [], []
        for c in classes:
            cdir = os.path.join(root, c)
            for fname in sorted(os.listdir(cdir)):
                if fname.lower().endswith(_IMG_EXTS):
                    paths.append(os.path.join(cdir, fname))
                    targets.append(class_to_idx[c])
        super().__init__(paths, targets, max(num_classes, len(classes)),
                         view, train_transform, limit=limit, seed=seed)
        self.classes = classes


class FileListDataset(_DiskImageDataset):
    """``path label`` per line (custom_imbalanced_imagenet.py:22-26)."""

    def __init__(self, root: str, list_file: str, view: ViewSpec,
                 train_transform: bool, num_classes: int = 1000,
                 limit: Optional[int] = None, seed: int = 0):
        paths, targets = [], []
        with open(list_file) as fh:
            for line in fh:
                parts = line.split()
                if len(parts) >= 2:
                    paths.append(os.path.join(root, parts[0]))
                    targets.append(int(parts[1]))
        super().__init__(paths, targets, num_classes, view, train_transform,
                         limit=limit, seed=seed)


def get_data_imagenet(data_path: str, debug_mode: bool = False, **_unused):
    """train/ and val/ subdirs (custom_imagenet.py:32-36)."""
    limit = 50 if debug_mode else None
    train_view = ViewSpec(IMAGENET_NORM, augment=True, pad=0)  # flip only
    val_view = ViewSpec(IMAGENET_NORM, augment=False)
    traindir = os.path.join(data_path, "train")
    valdir = os.path.join(data_path, "val")
    train_set = ImageFolderDataset(traindir, train_view, True, limit=limit)
    al_set = ImageFolderDataset(traindir, val_view, False, limit=limit)
    test_set = ImageFolderDataset(valdir, val_view, False, limit=limit)
    return train_set, test_set, al_set


def get_data_imbalanced_imagenet(data_path: str, debug_mode: bool = False,
                                 list_dir: Optional[str] = None, **_unused):
    """ImageNet-LT: file-list train/al over the train images + ImageFolder
    val (custom_imbalanced_imagenet.py:62-77)."""
    limit = 50 if debug_mode else None
    train_view = ViewSpec(IMAGENET_NORM, augment=True, pad=0)
    val_view = ViewSpec(IMAGENET_NORM, augment=False)
    list_dir = list_dir or os.path.join(data_path, "ImageNet_LT")
    train_list = os.path.join(list_dir, "ImageNet_LT_train.txt")
    train_set = FileListDataset(data_path, train_list, train_view, True,
                                limit=limit)
    al_set = FileListDataset(data_path, train_list, val_view, False,
                             limit=limit)
    test_set = ImageFolderDataset(os.path.join(data_path, "val"), val_view,
                                  False, limit=limit)
    return train_set, test_set, al_set


DATASETS.register("imagenet", get_data_imagenet)
DATASETS.register("imbalanced_imagenet", get_data_imbalanced_imagenet)
