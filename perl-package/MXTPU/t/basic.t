#!/usr/bin/env perl
# Executor forward/backward + kvstore sgd through the Perl binding.
use strict;
use warnings;
use Test::More tests => 8;
use FindBin;

BEGIN {
    $ENV{MXTPU_RT_HOME}     ||= "$FindBin::Bin/../../..";
    $ENV{MXTPU_RT_PLATFORM} ||= 'cpu';
}

use MXTPU;

is(MXTPU::rt_init(), 0, 'runtime init') or diag(MXTPU::last_error());

my $json = <<'JSON';
{"nodes": [
  {"op": "null", "name": "data", "attrs": {}, "inputs": []},
  {"op": "null", "name": "fc_weight", "attrs": {}, "inputs": []},
  {"op": "FullyConnected", "name": "fc",
   "attrs": {"num_hidden": "3", "no_bias": "True"},
   "inputs": [[0, 0, 0], [1, 0, 0]]},
  {"op": "null", "name": "softmax_label", "attrs": {}, "inputs": []},
  {"op": "SoftmaxOutput", "name": "softmax", "attrs": {},
   "inputs": [[2, 0, 0], [3, 0, 0]]}],
 "arg_nodes": [0, 1, 3],
 "heads": [[4, 0, 0]]}
JSON

my $exec = MXTPU::exec_create($json);
ok($exec > 0, 'exec_create') or diag(MXTPU::last_error());

is(MXTPU::exec_simple_bind($exec,
                           ['data', 'fc_weight', 'softmax_label'],
                           [[2, 4], [3, 4], [2]]),
   0, 'simple_bind') or diag(MXTPU::last_error());

MXTPU::exec_set_arg($exec, 'data',
                    pack('f*', 1, 0, 0, 0, 0, 1, 0, 0), [2, 4]);
MXTPU::exec_set_arg($exec, 'fc_weight',
                    pack('f*', (0.5) x 4, (0.1) x 4, (-0.2) x 4), [3, 4]);
MXTPU::exec_set_arg($exec, 'softmax_label', pack('f*', 0, 1), [2]);

is(MXTPU::exec_forward($exec, 1), 0, 'forward');
my @probs = unpack('f*', MXTPU::exec_output($exec, 0, 6));
ok(abs($probs[0] + $probs[1] + $probs[2] - 1.0) < 1e-4,
   'softmax rows sum to 1');

is(MXTPU::exec_backward($exec), 0, 'backward');
my @grad = unpack('f*', MXTPU::exec_grad($exec, 'fc_weight', 12));
my $gsum = 0; $gsum += abs($_) for @grad;
ok($gsum > 0, 'gradient flowed to fc_weight');

# kvstore: init 2.0, push grad 1.0 under sgd lr 0.5 -> pull 1.5
my $kv = MXTPU::kv_create('local');
MXTPU::kv_set_optimizer($kv, 'sgd', 0.5);
MXTPU::kv_init($kv, 1, pack('f*', (2.0) x 4), [4]);
MXTPU::kv_push($kv, 1, pack('f*', (1.0) x 4), [4]);
my @w = unpack('f*', MXTPU::kv_pull($kv, 1, 4));
ok(abs($w[0] - 1.5) < 1e-5, 'kvstore sgd update') or diag("got $w[0]");

MXTPU::rt_free($exec);
MXTPU::rt_free($kv);
