"""Dataset / DataLoader over DNDarrays (reference:
heat/utils/data/datatools.py, 376 LoC).

The reference wraps each rank's *local shard* as a torch dataset and performs
an **epoch-end global shuffle** by Alltoall-ing permuted samples between ranks
(``dataset_shuffle``/``dataset_ishuffle``, datatools.py:246, :301).  Here the
global array is shuffled with one sharded ``jax.random.permutation`` — the
same all-to-all, emitted by XLA — and batches are sliced off the sharded
array, so a batch is already distributed over the mesh when the train step
consumes it.
"""

from __future__ import annotations

from typing import Iterator, List, Optional, Sequence, Union

import numpy as np

import jax
import jax.numpy as jnp

from ...core import random as ht_random
from ...core import telemetry, types
from ...core.dndarray import DNDarray, _ensure_split

__all__ = ["Dataset", "DataLoader", "dataset_shuffle", "dataset_ishuffle", "dataset_irecv"]


class Dataset:
    """Dataset over one or more DNDarrays sharing the sample axis
    (reference: datatools.py:143).

    The reference's notion of "local shard as torch dataset" does not apply
    under the single-controller model; indexing is global."""

    def __init__(self, array: DNDarray, *arrays: DNDarray, transform=None,
                 transforms=None, ishuffle: bool = False, test_set: bool = False):
        self.arrays = (array,) + arrays
        n = array.shape[0]
        for a in self.arrays[1:]:
            if a.shape[0] != n:
                raise ValueError("all arrays must share the sample dimension")
        # reference spellings (datatools.py:143): ``transforms`` is one
        # callable per array, applied to that array's item; ``ishuffle``
        # selects the non-blocking epoch shuffle (same call under async
        # dispatch); ``test_set`` disables shuffling.  ``transform`` (ours)
        # receives the whole item tuple instead — mutually exclusive.
        if transform is not None and transforms is not None:
            raise ValueError("pass either transform (tuple-level) or transforms "
                             "(per-array), not both")
        if transforms is not None and not isinstance(transforms, (list, tuple)):
            transforms = [transforms]
        if transforms is not None:
            # pad once to one entry per array; __getitem__ just zips
            transforms = list(transforms) + [None] * (len(self.arrays) - len(transforms))
        self.transforms = transforms
        self.transform = transform
        self.ishuffle = ishuffle
        self.test_set = test_set

    def __len__(self) -> int:
        return self.arrays[0].shape[0]

    def __getitem__(self, index):
        items = tuple(a.larray[index] for a in self.arrays)
        if self.transforms is not None:
            # per-array transforms, reference contract (datatools.py:176)
            items = tuple(
                t(item) if t is not None else item
                for t, item in zip(self.transforms, items)
            )
            return items[0] if len(items) == 1 else items
        if self.transform is not None:
            return self.transform(*items)
        return items[0] if len(items) == 1 else items

    def shuffle(self) -> None:
        """Globally shuffle all arrays with one shared permutation
        (reference: dataset_shuffle, datatools.py:246).  A no-op for test
        sets, like the reference's guard (datatools.py:231)."""
        if self.test_set:
            return
        if all(a.split == 0 for a in self.arrays) and self.arrays:
            # sharded epoch shuffle: rows ride the distributed sort as
            # payloads — the reference's Alltoall (datatools.py:246)
            # without ever replicating the permutation or the data
            self.arrays = tuple(ht_random.shuffle_rows(list(self.arrays)))
            return
        n = len(self)
        perm = ht_random.randperm(n).larray
        new = []
        for a in self.arrays:
            shuffled = a.larray[perm]
            wrapped = DNDarray(
                shuffled, a.shape, a.dtype, a.split, a.device, a.comm
            )
            new.append(_ensure_split(wrapped, a.split))
        self.arrays = tuple(new)

    def Shuffle(self) -> None:
        """Reference spelling of the blocking epoch shuffle
        (datatools.py:196)."""
        self.shuffle()

    def Ishuffle(self) -> None:
        """Reference spelling of the non-blocking epoch shuffle
        (datatools.py:204); identical under JAX's async dispatch."""
        self.shuffle()


class DataLoader:
    """Iterates sharded batches of a Dataset/DNDarray (reference:
    datatools.py:16).

    Batches come off the sharded global array, so each device reads only its
    own rows; ``shuffle=True`` reshuffles globally every epoch, exactly the
    reference's epoch-end Alltoall."""

    def __init__(
        self,
        dataset: Union[Dataset, DNDarray],
        batch_size: int = 1,
        shuffle: bool = False,
        drop_last: bool = False,
        num_workers: int = 0,
        collate_fn=None,
        pin_memory: bool = False,
        timeout: float = 0,
        worker_init_fn=None,
    ):
        from .partial_dataset import PartialH5Dataset

        if isinstance(dataset, DNDarray):
            dataset = Dataset(dataset)
        # out-of-core path (reference: the loader drives PartialH5Dataset's
        # prefetch threads, partial_dataset.py:224): batches are streamed
        # slabs off the core engine, one per reader round-trip
        self._streaming = isinstance(dataset, PartialH5Dataset)
        self.dataset = dataset
        self.batch_size = int(batch_size)
        self.shuffle = shuffle
        self.drop_last = drop_last
        # torch-DataLoader knobs the reference forwards (datatools.py:16).
        # Worker processes/pinning don't exist in this IO model (batches are
        # device-resident slices); collate_fn is honored.
        self.num_workers = num_workers
        self.collate_fn = collate_fn
        self.pin_memory = pin_memory
        self.timeout = timeout
        self.worker_init_fn = worker_init_fn

    def __len__(self) -> int:
        n = len(self.dataset)
        if self._streaming:
            return -(-n // self.dataset.slab_rows)
        if self.drop_last:
            return n // self.batch_size
        return -(-n // self.batch_size)

    def __iter__(self) -> Iterator:
        if self._streaming:
            # slab-sized streamed batches; collate_fn still honored
            for batch in iter(self.dataset):
                yield self.collate_fn(batch) if self.collate_fn is not None else batch
            return
        if self.shuffle:
            self.dataset.shuffle()  # no-op for test_set datasets
        n = len(self.dataset)
        nbatches = len(self)
        for i in range(nbatches):
            lo = i * self.batch_size
            hi = min(lo + self.batch_size, n)
            batch = self.dataset[lo:hi]
            yield self.collate_fn(batch) if self.collate_fn is not None else batch


def dataset_shuffle(dataset: Dataset, attrs: Optional[List] = None) -> None:
    """Global in-place shuffle (reference: datatools.py:246)."""
    dataset.shuffle()


def dataset_ishuffle(dataset: Dataset, attrs: Optional[List] = None) -> None:
    """Non-blocking shuffle (reference: datatools.py:301). JAX dispatch is
    asynchronous already, so this is the same call."""
    dataset.shuffle()


def dataset_irecv(dataset: Dataset) -> None:
    """Complete a pending :func:`dataset_ishuffle` (reference:
    datatools.py:343 waits on the Irecv handles posted by ishuffle).  JAX's
    async dispatch plays the role of the Irecv ring, so completing means
    draining the device queue for the shuffled arrays."""
    import jax

    for a in dataset.arrays:
        with telemetry.sync("data.ingest_barrier"):  # before epoch timing starts
            jax.block_until_ready(a.larray)
