#!/usr/bin/env python
"""im2rec — convert an image list/folder into RecordIO
(reference ``tools/im2rec.py``, C++ twin ``tools/im2rec.cc``).

List file format (same as reference): ``index\\tlabel[\\tlabel...]\\tpath``.
"""
import argparse
import os
import random
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

# Host-side dataset tool: packing needs only the CPU, and a chip
# belongs to one process at a time, so never claim it here.
from mxnet_tpu.base import force_cpu_backend
force_cpu_backend()

import numpy as np


def list_image(root, recursive, exts):
    i = 0
    if recursive:
        cat = {}
        for path, dirs, files in os.walk(root, followlinks=True):
            dirs.sort()
            files.sort()
            for fname in files:
                fpath = os.path.join(path, fname)
                suffix = os.path.splitext(fname)[1].lower()
                if os.path.isfile(fpath) and (suffix in exts):
                    if path not in cat:
                        cat[path] = len(cat)
                    yield (i, os.path.relpath(fpath, root), cat[path])
                    i += 1
    else:
        for fname in sorted(os.listdir(root)):
            fpath = os.path.join(root, fname)
            suffix = os.path.splitext(fname)[1].lower()
            if os.path.isfile(fpath) and (suffix in exts):
                yield (i, os.path.relpath(fpath, root), 0)
                i += 1


def write_list(path_out, image_list):
    with open(path_out, 'w') as fout:
        for i, item in enumerate(image_list):
            line = '%d\t' % item[0]
            for j in item[2:]:
                line += '%f\t' % j
            line += '%s\n' % item[1]
            fout.write(line)


def read_list(path_in):
    with open(path_in) as fin:
        while True:
            line = fin.readline()
            if not line:
                break
            line = [i.strip() for i in line.strip().split('\t')]
            line_len = len(line)
            if line_len < 3:
                continue
            item = [int(line[0])] + [line[-1]] + \
                [float(i) for i in line[1:-1]]
            yield item


def image_encode(args, i, item, q_out):
    from mxnet_tpu import recordio
    from PIL import Image
    fullpath = os.path.join(args.root, item[1])
    if len(item) > 3:
        header = recordio.IRHeader(0, np.asarray(item[2:], np.float32),
                                   item[0], 0)
    else:
        header = recordio.IRHeader(0, item[2], item[0], 0)
    try:
        img = Image.open(fullpath).convert('RGB')
    except Exception as e:
        print('imread error: %s %s' % (fullpath, e))
        q_out.append((i, None, item))
        return
    if args.resize:
        w, h = img.size
        if min(w, h) > args.resize:
            if w > h:
                newsize = (int(w * args.resize / h), args.resize)
            else:
                newsize = (args.resize, int(h * args.resize / w))
            img = img.resize(newsize, Image.BILINEAR)
    s = recordio.pack_img(header, np.asarray(img),
                          quality=args.quality, img_fmt=args.encoding)
    q_out.append((i, s, item))


def make_rec(args, image_list):
    """Pack the list into .rec/.idx.  With --num-thread > 1 the
    decode/resize/JPEG-encode stage fans out over a thread pool (PIL
    releases the GIL in its codecs) while the single writer keeps
    records in list order — the role of the reference's OMP-parallel
    ``tools/im2rec.cc``."""
    from mxnet_tpu import recordio
    fname_rec = os.path.splitext(args.prefix)[0] + '.rec'
    fname_idx = os.path.splitext(args.prefix)[0] + '.idx'
    record = recordio.MXIndexedRecordIO(fname_idx, fname_rec, 'w')
    cnt = 0

    def encoded(i, item):
        out = []
        image_encode(args, i, item, out)
        return out[0]

    nthread = max(1, int(getattr(args, 'num_thread', 1)))
    pool = None
    if nthread == 1:
        results = (encoded(i, item)
                   for i, item in enumerate(image_list))
    else:
        from concurrent.futures import ThreadPoolExecutor
        pool = ThreadPoolExecutor(nthread)
        # bounded window keeps memory flat on ImageNet-scale lists
        results = _ordered_window(
            pool, encoded, enumerate(image_list), window=nthread * 4)
    try:
        for _, s, it in results:
            if s is None:
                continue
            record.write_idx(it[0], s)
            cnt += 1
            if cnt % 1000 == 0:
                print('processed', cnt)
    finally:
        # an encode error mid-run must still save the .idx and upload
        # any remote spool for the records already written
        if pool is not None:
            pool.shutdown(wait=False, cancel_futures=True)
        record.close()
    print('wrote %d records to %s' % (cnt, fname_rec))


def _ordered_window(pool, fn, items, window):
    """Yield fn(i, item) results in order with at most ``window``
    submissions in flight."""
    from collections import deque
    pending = deque()
    it = iter(items)
    exhausted = False
    while True:
        while not exhausted and len(pending) < window:
            try:
                i, item = next(it)
            except StopIteration:
                exhausted = True
                break
            pending.append(pool.submit(fn, i, item))
        if not pending:
            return
        yield pending.popleft().result()


def main():
    parser = argparse.ArgumentParser(
        description='Create an image list / RecordIO file')
    parser.add_argument('prefix', help='prefix of output list/rec files')
    parser.add_argument('root', help='path to folder containing images')
    parser.add_argument('--list', action='store_true',
                        help='create image list instead of rec')
    parser.add_argument('--exts', nargs='+',
                        default=['.jpeg', '.jpg', '.png'])
    parser.add_argument('--recursive', action='store_true')
    parser.add_argument('--shuffle', type=bool, default=True)
    parser.add_argument('--resize', type=int, default=0)
    parser.add_argument('--quality', type=int, default=95)
    parser.add_argument('--encoding', type=str, default='.jpg')
    parser.add_argument('--num-thread', type=int, default=1,
                        help='parallel encode workers (the im2rec.cc '
                             'OMP analogue); writes stay in order')
    args = parser.parse_args()

    if args.list:
        image_list = list(list_image(args.root, args.recursive, args.exts))
        if args.shuffle:
            random.seed(100)
            random.shuffle(image_list)
        write_list(args.prefix + '.lst', image_list)
    else:
        lst = args.prefix + '.lst'
        if os.path.isfile(lst):
            image_list = read_list(lst)
        else:
            image_list = [(i, p, l) for i, p, l in
                          list_image(args.root, args.recursive, args.exts)]
            if args.shuffle:
                random.seed(100)
                random.shuffle(image_list)
        make_rec(args, image_list)


if __name__ == '__main__':
    main()
