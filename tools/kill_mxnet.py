#!/usr/bin/env python
"""Kill stray framework processes on this host (reference
tools/kill-mxnet.py).  The reference pkills worker/server/scheduler
processes left behind by a crashed dist job; here the same cleanup
covers launcher-spawned ranks (tools/launch.py) and stuck bench runs.
"""
import argparse
import os
import signal
import subprocess
import sys


PATTERNS = ['mxnet_tpu', 'launch.py', 'train_imagenet', 'train_mnist',
            'train_cifar10']


def _ancestors():
    """PIDs of this process and its ancestors (never kill those)."""
    out = set()
    pid = os.getpid()
    while pid > 1:
        out.add(pid)
        try:
            with open('/proc/%d/stat' % pid) as f:
                pid = int(f.read().rsplit(')', 1)[1].split()[1])
        except (OSError, ValueError, IndexError):
            break
    return out


def find_pids(patterns):
    out = subprocess.run(['ps', '-eo', 'pid,args'], capture_output=True,
                         text=True).stdout
    skip = _ancestors()
    pids = []
    for line in out.splitlines()[1:]:
        line = line.strip()
        if not line:
            continue
        pid_s, _, cmd = line.partition(' ')
        try:
            pid = int(pid_s)
        except ValueError:
            continue
        if pid in skip or 'kill_mxnet' in cmd:
            continue
        argv0 = cmd.split()[0] if cmd.split() else ''
        # only direct python invocations of framework scripts — never
        # shells or other tools whose command line merely mentions them
        if os.path.basename(argv0).startswith('python') and \
                any(p in cmd for p in patterns):
            pids.append((pid, cmd.strip()))
    return pids


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('pattern', nargs='?', default=None,
                        help='extra substring to match')
    parser.add_argument('--dry-run', action='store_true')
    parser.add_argument('-9', dest='force', action='store_true',
                        help='SIGKILL instead of SIGTERM')
    args = parser.parse_args()
    patterns = PATTERNS + ([args.pattern] if args.pattern else [])
    pids = find_pids(patterns)
    if not pids:
        print('no matching processes')
        return 0
    sig = signal.SIGKILL if args.force else signal.SIGTERM
    for pid, cmd in pids:
        print('%s %d  %s' % ('would kill' if args.dry_run else 'killing',
                             pid, cmd[:100]))
        if not args.dry_run:
            try:
                os.kill(pid, sig)
            except ProcessLookupError:
                pass
    return 0


if __name__ == '__main__':
    sys.exit(main())
