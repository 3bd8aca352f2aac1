// dynamo-store: native coordinator for the distributed runtime.
//
// C++ implementation of the control plane the Python StoreServer exposes
// (dynamo_tpu/store/{server,memory}.py is the semantic reference; the
// upstream system this replaces is the reference's etcd+NATS pair,
// lib/runtime/src/transports/{etcd,nats}.rs). Wire-compatible with
// dynamo_tpu/store/client.py: 4-byte LE length-prefixed msgpack frames,
// request {i, op, a}, unary reply {i, ok, v|e}, stream push {i: sid, s},
// stream end {i: sid, end: true}.
//
// Single-threaded poll(2) event loop; a 100ms tick drives lease expiry,
// queue redelivery, and blocked-pop timeouts. A dropped connection
// revokes its leases (liveness), closes its streams, and abandons its
// parked queue pops — identical semantics to the Python server.
//
// Build: g++ -O2 -std=c++17 -o dynamo_store store_server.cc

#include <algorithm>
#include <chrono>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <deque>
#include <map>
#include <memory>
#include <set>
#include <stdexcept>
#include <string>
#include <unordered_map>
#include <vector>

#include <arpa/inet.h>
#include <errno.h>
#include <fcntl.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <poll.h>
#include <signal.h>
#include <sys/socket.h>
#include <unistd.h>

#include "msgpack.h"

// ---------------------------------------------------------------------------
// Store state
// ---------------------------------------------------------------------------

static double now_s() {
  using namespace std::chrono;
  return duration<double>(steady_clock::now().time_since_epoch()).count();
}

static bool subject_matches(const std::string& pattern, const std::string& subject) {
  // NATS-style: '.'-separated tokens, '*' = one token, '>' = 1+ trailing
  if (pattern.find('*') == std::string::npos && pattern.find('>') == std::string::npos)
    return pattern == subject;
  auto split = [](const std::string& s) {
    std::vector<std::string> out;
    size_t start = 0;
    while (true) {
      size_t dot = s.find('.', start);
      if (dot == std::string::npos) { out.push_back(s.substr(start)); break; }
      out.push_back(s.substr(start, dot - start));
      start = dot + 1;
    }
    return out;
  };
  auto pt = split(pattern), st = split(subject);
  for (size_t i = 0; i < pt.size(); ++i) {
    if (pt[i] == ">") return st.size() >= i + 1;
    if (i >= st.size()) return false;
    if (pt[i] != "*" && pt[i] != st[i]) return false;
  }
  return pt.size() == st.size();
}

static volatile sig_atomic_t g_stop = 0;
static void on_term(int) { g_stop = 1; }

struct Conn;  // fwd

struct Entry {
  std::string value;
  int64_t version = 0;
  int64_t lease_id = 0;
};

struct Lease {
  double ttl_s = 0;
  double expires_at = 0;
  std::set<std::string> keys;
};

struct QMsg {
  int64_t id;
  std::string payload;
};

struct ParkedPop {
  Conn* conn;
  int64_t rid;
  double deadline;   // <0: no timeout
  double visibility;
  uint64_t order;
};

struct QueueState {
  int64_t next_id = 1;
  std::deque<QMsg> ready;
  std::map<int64_t, std::pair<QMsg, double>> in_flight;  // id -> (msg, redeliver at)
  std::deque<ParkedPop> parked;
};

struct WatchReg {
  Conn* conn;
  int64_t sid;
  std::string prefix;
};

struct SubReg {
  Conn* conn;
  int64_t sid;
  std::string pattern;
};

struct Conn {
  int fd;
  std::string inbuf;
  std::string outbuf;
  std::set<int64_t> leases;
  std::set<int64_t> stream_ids;
  bool dead = false;
};

struct Server {
  int listen_fd = -1;
  std::map<int, std::unique_ptr<Conn>> conns;
  // kv
  std::map<std::string, Entry> kv;  // ordered: prefix scans
  int64_t version = 0;
  // leases
  std::unordered_map<int64_t, Lease> leases;
  int64_t next_lease = 1;
  // streams
  std::vector<WatchReg> watches;
  std::vector<SubReg> subs;
  int64_t next_sid = 1;
  // queues / objects
  std::unordered_map<std::string, QueueState> queues;
  std::unordered_map<std::string, std::map<std::string, std::string>> objects;
  uint64_t pop_order = 0;
  // durability — same restart CONTRACT as the python store
  // (store/persist.py: unleased KV, queues with in-flight restored as
  // ready, the object plane; leased liveness keys ephemeral) and the
  // same MECHANISM: every surviving mutation appends one WAL record
  // (flushed before the reply is sent — kernel-buffered, so it
  // survives a kill -9; --fsync-wal additionally fsyncs per record for
  // power-loss durability, like etcd's raft log fsync). Snapshots
  // (2s tick + SIGTERM) act as WAL compaction: a successful snapshot
  // truncates the log. Replay order on boot: snapshot, then WAL
  // records; q_push records already folded into the snapshot
  // (id < its next_id) are skipped so queued work never delivers
  // twice. Reference role: etcd raft log + JetStream file store
  // (lib/runtime/src/transports/{etcd,nats}.rs).
  std::string persist_path;
  std::string wal_path;
  FILE* wal = nullptr;
  bool fsync_wal = false;
  bool dirty = false;
  double last_snap = 0;
  double last_sweep = 0;

  // ---- framing ----------------------------------------------------------
  void send_frame(Conn* c, const Val& v) {
    if (c->dead) return;
    std::string body;
    encode(v, body);
    uint32_t len = (uint32_t)body.size();
    char hdr[4];
    hdr[0] = (char)(len & 0xff);
    hdr[1] = (char)((len >> 8) & 0xff);
    hdr[2] = (char)((len >> 16) & 0xff);
    hdr[3] = (char)((len >> 24) & 0xff);
    c->outbuf.append(hdr, 4);
    c->outbuf += body;
  }

  void reply_ok(Conn* c, int64_t rid, Val v) {
    Val r = Val::map();
    r.m.emplace_back("i", Val::integer(rid));
    r.m.emplace_back("ok", Val::boolean(true));
    r.m.emplace_back("v", std::move(v));
    send_frame(c, r);
  }

  void reply_err(Conn* c, int64_t rid, const std::string& msg) {
    Val r = Val::map();
    r.m.emplace_back("i", Val::integer(rid));
    r.m.emplace_back("ok", Val::boolean(false));
    r.m.emplace_back("e", Val::str(msg));
    send_frame(c, r);
  }

  void push_stream(Conn* c, int64_t sid, Val item) {
    Val r = Val::map();
    r.m.emplace_back("i", Val::integer(sid));
    r.m.emplace_back("s", std::move(item));
    send_frame(c, r);
  }

  // ---- kv ---------------------------------------------------------------
  static Val enc_entry(const std::string& key, const Entry& e) {
    Val v = Val::map();
    v.m.emplace_back("k", Val::str(key));
    v.m.emplace_back("v", Val::bin(e.value));
    v.m.emplace_back("ver", Val::integer(e.version));
    v.m.emplace_back("l", Val::integer(e.lease_id));
    return v;
  }

  void emit_watch(const char* type, const std::string& key, const Entry& e) {
    for (auto& w : watches) {
      if (key.rfind(w.prefix, 0) == 0) {
        Val ev = Val::map();
        ev.m.emplace_back("t", Val::str(type));
        ev.m.emplace_back("e", enc_entry(key, e));
        push_stream(w.conn, w.sid, std::move(ev));
      }
    }
  }

  int64_t kv_put(const std::string& key, std::string value, int64_t lease_id) {
    auto prev = kv.find(key);
    bool durable_prev = prev != kv.end() && prev->second.lease_id == 0;
    if (prev != kv.end() && prev->second.lease_id != lease_id) {
      auto old = leases.find(prev->second.lease_id);
      if (old != leases.end()) old->second.keys.erase(key);
    }
    if (lease_id != 0) {
      auto it = leases.find(lease_id);
      if (it == leases.end()) throw std::runtime_error("KeyError: lease does not exist");
      it->second.keys.insert(key);
    }
    Entry e{std::move(value), ++version, lease_id};
    kv[key] = e;
    if (lease_id == 0) {
      dirty = true;
      wal_kv_put(key, e.version, e.value);
    } else if (durable_prev) {
      // a leased put SHADOWS a previously durable key: tombstone it,
      // or a restart would resurrect the stale value
      dirty = true;
      wal_kv_del(key);
    }
    emit_watch("put", key, e);
    return e.version;
  }

  bool kv_delete(const std::string& key) {
    auto it = kv.find(key);
    if (it == kv.end()) return false;
    Entry e = std::move(it->second);
    kv.erase(it);
    if (e.lease_id == 0) {
      dirty = true;
      wal_kv_del(key);
    }
    if (e.lease_id != 0) {
      auto l = leases.find(e.lease_id);
      if (l != leases.end()) l->second.keys.erase(key);
    }
    emit_watch("delete", key, e);
    return true;
  }

  void lease_revoke(int64_t lid) {
    auto it = leases.find(lid);
    if (it == leases.end()) return;
    std::vector<std::string> keys(it->second.keys.begin(), it->second.keys.end());
    leases.erase(it);
    for (auto& k : keys) kv_delete(k);
  }

  // ---- queues -----------------------------------------------------------
  static Val enc_qmsg(const QMsg& m) {
    Val v = Val::map();
    v.m.emplace_back("id", Val::integer(m.id));
    v.m.emplace_back("p", Val::bin(m.payload));
    return v;
  }

  void serve_parked(const std::string& qname) {
    auto& q = queues[qname];
    while (!q.ready.empty() && !q.parked.empty()) {
      ParkedPop pp = q.parked.front();
      q.parked.pop_front();
      if (pp.conn->dead) continue;
      QMsg msg = std::move(q.ready.front());
      q.ready.pop_front();
      Val v = enc_qmsg(msg);
      q.in_flight[msg.id] = {std::move(msg), now_s() + pp.visibility};
      reply_ok(pp.conn, pp.rid, std::move(v));
    }
  }

  // ---- request dispatch -------------------------------------------------
  void handle(Conn* c, const Val& msg) {
    const Val* iv = msg.get("i");
    const Val* opv = msg.get("op");
    if (!iv || !opv) return;  // malformed; drop
    int64_t rid = iv->i;
    const std::string& op = opv->s;
    const Val* av = msg.get("a");
    static const Val empty_arr = Val::arr();
    const Val& args = av ? *av : empty_arr;
    auto arg = [&](size_t k) -> const Val& {
      static Val nil_v;
      return k < args.a.size() ? args.a[k] : nil_v;
    };
    try {
      if (op == "ping") {
        reply_ok(c, rid, Val::str("pong"));
      } else if (op == "kv_put") {
        reply_ok(c, rid, Val::integer(kv_put(arg(0).s, arg(1).s, arg(2).i)));
      } else if (op == "kv_create") {
        if (kv.count(arg(0).s)) reply_ok(c, rid, Val::boolean(false));
        else {
          kv_put(arg(0).s, arg(1).s, arg(2).i);
          reply_ok(c, rid, Val::boolean(true));
        }
      } else if (op == "kv_get") {
        auto it = kv.find(arg(0).s);
        reply_ok(c, rid, it == kv.end() ? Val::nil() : enc_entry(it->first, it->second));
      } else if (op == "kv_get_prefix") {
        Val out = Val::arr();
        const std::string& prefix = arg(0).s;
        for (auto it = kv.lower_bound(prefix);
             it != kv.end() && it->first.rfind(prefix, 0) == 0; ++it)
          out.a.push_back(enc_entry(it->first, it->second));
        reply_ok(c, rid, std::move(out));
      } else if (op == "kv_delete") {
        reply_ok(c, rid, Val::boolean(kv_delete(arg(0).s)));
      } else if (op == "kv_delete_prefix") {
        const std::string& prefix = arg(0).s;
        std::vector<std::string> keys;
        for (auto it = kv.lower_bound(prefix);
             it != kv.end() && it->first.rfind(prefix, 0) == 0; ++it)
          keys.push_back(it->first);
        for (auto& k : keys) kv_delete(k);
        reply_ok(c, rid, Val::integer((int64_t)keys.size()));
      } else if (op == "watch_prefix") {
        int64_t sid = next_sid++;
        const std::string& prefix = arg(0).s;
        Val snapshot = Val::arr();
        for (auto it = kv.lower_bound(prefix);
             it != kv.end() && it->first.rfind(prefix, 0) == 0; ++it)
          snapshot.a.push_back(enc_entry(it->first, it->second));
        watches.push_back({c, sid, prefix});
        c->stream_ids.insert(sid);
        Val v = Val::map();
        v.m.emplace_back("sid", Val::integer(sid));
        v.m.emplace_back("snapshot", std::move(snapshot));
        reply_ok(c, rid, std::move(v));
      } else if (op == "lease_grant") {
        int64_t lid = next_lease++;
        double ttl = arg(0).num();
        leases[lid] = Lease{ttl, now_s() + ttl, {}};
        c->leases.insert(lid);
        reply_ok(c, rid, Val::integer(lid));
      } else if (op == "lease_keepalive") {
        auto it = leases.find(arg(0).i);
        if (it == leases.end()) reply_ok(c, rid, Val::boolean(false));
        else {
          it->second.expires_at = now_s() + it->second.ttl_s;
          reply_ok(c, rid, Val::boolean(true));
        }
      } else if (op == "lease_revoke") {
        lease_revoke(arg(0).i);
        c->leases.erase(arg(0).i);
        reply_ok(c, rid, Val::boolean(true));
      } else if (op == "publish") {
        const std::string& subject = arg(0).s;
        for (auto& s : subs) {
          if (subject_matches(s.pattern, subject)) {
            Val item = Val::map();
            item.m.emplace_back("subj", Val::str(subject));
            item.m.emplace_back("p", Val::bin(arg(1).s));
            push_stream(s.conn, s.sid, std::move(item));
          }
        }
        reply_ok(c, rid, Val::boolean(true));
      } else if (op == "subscribe") {
        int64_t sid = next_sid++;
        subs.push_back({c, sid, arg(0).s});
        c->stream_ids.insert(sid);
        Val v = Val::map();
        v.m.emplace_back("sid", Val::integer(sid));
        reply_ok(c, rid, std::move(v));
      } else if (op == "stream_close") {
        close_stream(c, arg(0).i, /*notify_end=*/true);
        reply_ok(c, rid, Val::boolean(true));
      } else if (op == "queue_push") {
        auto& q = queues[arg(0).s];
        QMsg msg{q.next_id++, arg(1).s};
        int64_t id = msg.id;
        wal_q_push(arg(0).s, id, msg.payload);
        q.ready.push_back(std::move(msg));
        dirty = true;
        serve_parked(arg(0).s);
        reply_ok(c, rid, Val::integer(id));
      } else if (op == "queue_pop") {
        const std::string& qname = arg(0).s;
        auto& q = queues[qname];
        double visibility = arg(2).is_num() ? arg(2).num() : 30.0;
        if (!q.ready.empty()) {
          QMsg msg = std::move(q.ready.front());
          q.ready.pop_front();
          Val v = enc_qmsg(msg);
          q.in_flight[msg.id] = {std::move(msg), now_s() + visibility};
          reply_ok(c, rid, std::move(v));
        } else {
          double deadline = arg(1).is_num() ? now_s() + arg(1).num() : -1.0;
          if (arg(1).is_num() && arg(1).num() <= 0) reply_ok(c, rid, Val::nil());
          else q.parked.push_back({c, rid, deadline, visibility, pop_order++});
        }
      } else if (op == "queue_ack") {
        auto& q = queues[arg(0).s];
        bool acked = q.in_flight.erase(arg(1).i) > 0;
        if (acked) {
          dirty = true;
          wal_q_ack(arg(0).s, arg(1).i);
        }
        reply_ok(c, rid, Val::boolean(acked));
      } else if (op == "queue_len") {
        auto& q = queues[arg(0).s];
        reply_ok(c, rid,
                 Val::integer((int64_t)(q.ready.size() + q.in_flight.size())));
      } else if (op == "obj_put") {
        objects[arg(0).s][arg(1).s] = arg(2).s;
        dirty = true;
        wal_obj_put(arg(0).s, arg(1).s, arg(2).s);
        reply_ok(c, rid, Val::boolean(true));
      } else if (op == "obj_get") {
        auto b = objects.find(arg(0).s);
        if (b == objects.end()) { reply_ok(c, rid, Val::nil()); return; }
        auto o = b->second.find(arg(1).s);
        reply_ok(c, rid, o == b->second.end() ? Val::nil() : Val::bin(o->second));
      } else if (op == "obj_delete") {
        auto b = objects.find(arg(0).s);
        bool deleted = b != objects.end() && b->second.erase(arg(1).s) > 0;
        if (deleted) {
          dirty = true;
          wal_obj_del(arg(0).s, arg(1).s);
        }
        reply_ok(c, rid, Val::boolean(deleted));
      } else if (op == "obj_list") {
        Val out = Val::arr();
        auto b = objects.find(arg(0).s);
        if (b != objects.end())
          for (auto& kv2 : b->second) out.a.push_back(Val::str(kv2.first));
        reply_ok(c, rid, std::move(out));
      } else {
        reply_err(c, rid, "ValueError: unknown op '" + op + "'");
      }
    } catch (const std::exception& e) {
      reply_err(c, rid, e.what());
    }
  }

  void close_stream(Conn* c, int64_t sid, bool notify_end) {
    c->stream_ids.erase(sid);
    watches.erase(std::remove_if(watches.begin(), watches.end(),
                                 [&](const WatchReg& w) {
                                   return w.conn == c && w.sid == sid;
                                 }),
                  watches.end());
    subs.erase(std::remove_if(subs.begin(), subs.end(),
                              [&](const SubReg& s) {
                                return s.conn == c && s.sid == sid;
                              }),
               subs.end());
    if (notify_end) {
      Val r = Val::map();
      r.m.emplace_back("i", Val::integer(sid));
      r.m.emplace_back("end", Val::boolean(true));
      send_frame(c, r);
    }
  }

  // ---- durability -------------------------------------------------------
  // Binary snapshot, atomic tmp+rename. Format (all ints little-endian):
  //   "DTPUSNAP1" | u64 version
  //   u32 n_kv    | { str key | u64 ver | str value }       (unleased only)
  //   u32 n_queue | { str name | u64 next_id | u32 n | { u64 id | str p } }
  //   u32 n_bkt   | { str bucket | u32 n | { str name | str data } }
  static void put_u32(std::string& b, uint32_t v) { b.append((char*)&v, 4); }
  static void put_u64(std::string& b, uint64_t v) { b.append((char*)&v, 8); }
  static void put_str(std::string& b, const std::string& s) {
    put_u32(b, (uint32_t)s.size());
    b.append(s);
  }
  struct Rd {
    const std::string& b;
    size_t off = 0;
    bool ok = true;
    uint32_t u32() {
      if (off + 4 > b.size()) { ok = false; return 0; }
      uint32_t v; memcpy(&v, b.data() + off, 4); off += 4; return v;
    }
    uint64_t u64() {
      if (off + 8 > b.size()) { ok = false; return 0; }
      uint64_t v; memcpy(&v, b.data() + off, 8); off += 8; return v;
    }
    std::string str() {
      uint32_t n = u32();
      if (!ok || off + n > b.size()) { ok = false; return {}; }
      std::string s = b.substr(off, n); off += n; return s;
    }
  };

  // ---- write-ahead log --------------------------------------------------
  // Record: u32 body_len | u8 op | op fields (strings are u32-prefixed).
  // Ops: 1 kv_put(key, u64 ver, value)  2 kv_del(key)
  //      3 q_push(name, u64 id, payload) 4 q_ack(name, u64 id)
  //      5 obj_put(bucket, name, data)   6 obj_del(bucket, name)
  enum { W_KV_PUT = 1, W_KV_DEL, W_Q_PUSH, W_Q_ACK, W_OBJ_PUT, W_OBJ_DEL };

  void wal_write(const std::string& body) {
    if (wal_path.empty()) return;
    if (!wal) {
      wal = fopen(wal_path.c_str(), "ab");
      if (!wal) { perror("wal open"); return; }
    }
    std::string rec;
    put_u32(rec, (uint32_t)body.size());
    rec += body;
    // flush before the reply goes out: acked mutations survive a
    // process kill. --fsync-wal extends that to host/power crashes.
    bool ok = fwrite(rec.data(), 1, rec.size(), wal) == rec.size();
    ok = (fflush(wal) == 0) && ok;
    if (fsync_wal) ok = (fsync(fileno(wal)) == 0) && ok;
    if (!ok) {
      // A short/failed write (ENOSPC, EIO) may leave a TORN RECORD in
      // the middle of the log — replay stops at the first bad record,
      // so every later append would be silently lost on restart.
      // Force an immediate snapshot instead: it captures current state
      // (including this mutation) and truncates the broken log.
      perror("wal write (forcing snapshot)");
      fclose(wal);
      wal = nullptr;
      dirty = true;
      save_snapshot();  // retries via the 2s tick if it also fails
    }
  }

  void wal_kv_put(const std::string& key, int64_t ver, const std::string& value) {
    if (wal_path.empty()) return;
    std::string b(1, (char)W_KV_PUT);
    put_str(b, key); put_u64(b, (uint64_t)ver); put_str(b, value);
    wal_write(b);
  }
  void wal_kv_del(const std::string& key) {
    if (wal_path.empty()) return;
    std::string b(1, (char)W_KV_DEL);
    put_str(b, key);
    wal_write(b);
  }
  void wal_q_push(const std::string& q, int64_t id, const std::string& payload) {
    if (wal_path.empty()) return;
    std::string b(1, (char)W_Q_PUSH);
    put_str(b, q); put_u64(b, (uint64_t)id); put_str(b, payload);
    wal_write(b);
  }
  void wal_q_ack(const std::string& q, int64_t id) {
    if (wal_path.empty()) return;
    std::string b(1, (char)W_Q_ACK);
    put_str(b, q); put_u64(b, (uint64_t)id);
    wal_write(b);
  }
  void wal_obj_put(const std::string& bucket, const std::string& name,
                   const std::string& data) {
    if (wal_path.empty()) return;
    std::string b(1, (char)W_OBJ_PUT);
    put_str(b, bucket); put_str(b, name); put_str(b, data);
    wal_write(b);
  }
  void wal_obj_del(const std::string& bucket, const std::string& name) {
    if (wal_path.empty()) return;
    std::string b(1, (char)W_OBJ_DEL);
    put_str(b, bucket); put_str(b, name);
    wal_write(b);
  }

  void wal_truncate() {
    if (wal_path.empty()) return;
    if (wal) { fclose(wal); wal = nullptr; }
    FILE* t = fopen(wal_path.c_str(), "wb");
    if (t) {
      fflush(t);
      fsync(fileno(t));
      fclose(t);
    }
  }

  void replay_wal(const std::unordered_map<std::string, int64_t>& snap_next) {
    if (wal_path.empty()) return;
    FILE* f = fopen(wal_path.c_str(), "rb");
    if (!f) return;
    std::string b;
    char buf[1 << 16];
    size_t n;
    while ((n = fread(buf, 1, sizeof buf, f)) > 0) b.append(buf, n);
    fclose(f);
    std::unordered_map<std::string, std::set<int64_t>> acked;
    std::unordered_map<std::string, std::deque<QMsg>> pushes;
    std::unordered_map<std::string, int64_t> q_next;
    size_t off = 0;
    size_t n_rec = 0;
    while (off + 4 <= b.size()) {
      uint32_t len;
      memcpy(&len, b.data() + off, 4);
      if (off + 4 + len > b.size() || len == 0) break;  // torn tail: stop
      Rd r{b, off + 4};
      size_t end = off + 4 + len;
      uint8_t op = (uint8_t)b[r.off++];
      if (op == W_KV_PUT) {
        std::string key = r.str();
        int64_t ver = (int64_t)r.u64();
        std::string val = r.str();
        if (r.ok) {
          kv[key] = Entry{std::move(val), ver, 0};
          version = std::max(version, ver);
        }
      } else if (op == W_KV_DEL) {
        std::string key = r.str();
        if (r.ok) kv.erase(key);
      } else if (op == W_Q_PUSH) {
        std::string qn = r.str();
        int64_t id = (int64_t)r.u64();
        std::string payload = r.str();
        if (r.ok) {
          // records already folded into the snapshot (id < its
          // next_id) must not replay: queued work would deliver twice
          auto sn = snap_next.find(qn);
          if (sn == snap_next.end() || id >= sn->second) {
            pushes[qn].push_back(QMsg{id, std::move(payload)});
            auto& nx = q_next[qn];
            nx = std::max(nx, id + 1);
          }
        }
      } else if (op == W_Q_ACK) {
        std::string qn = r.str();
        int64_t id = (int64_t)r.u64();
        if (r.ok) acked[qn].insert(id);
      } else if (op == W_OBJ_PUT) {
        std::string bucket = r.str();
        std::string name = r.str();
        std::string data = r.str();
        if (r.ok) objects[bucket][name] = std::move(data);
      } else if (op == W_OBJ_DEL) {
        std::string bucket = r.str();
        std::string name = r.str();
        if (r.ok) {
          auto it = objects.find(bucket);
          if (it != objects.end()) it->second.erase(name);
        }
      } else {
        break;  // unknown op: stop replay (forward-compat guard)
      }
      if (!r.ok) break;
      off = end;
      ++n_rec;
    }
    for (auto& pe : pushes) {
      auto& q = queues[pe.first];
      auto& ack = acked[pe.first];
      for (auto& m : pe.second)
        if (!ack.count(m.id)) q.ready.push_back(std::move(m));
    }
    for (auto& ne : q_next) {
      auto& q = queues[ne.first];
      q.next_id = std::max(q.next_id, ne.second);
    }
    // acks may target messages restored from the SNAPSHOT
    for (auto& ae : acked) {
      auto qi = queues.find(ae.first);
      if (qi == queues.end()) continue;
      auto& ready = qi->second.ready;
      ready.erase(
          std::remove_if(ready.begin(), ready.end(),
                         [&](const QMsg& m) { return ae.second.count(m.id) > 0; }),
          ready.end());
    }
    if (n_rec > 0) dirty = true;  // compact replayed records on first tick
    if (off < b.size())
      fprintf(stderr, "persist: torn WAL tail at %zu/%zu (stopped replay)\n",
              off, b.size());
  }

  void save_snapshot() {
    if (persist_path.empty()) return;
    std::string b;
    b.append("DTPUSNAP1");
    put_u64(b, (uint64_t)version);
    uint32_t n_kv = 0;
    for (auto& e : kv) if (e.second.lease_id == 0) ++n_kv;
    put_u32(b, n_kv);
    for (auto& e : kv) {
      if (e.second.lease_id != 0) continue;
      put_str(b, e.first);
      put_u64(b, (uint64_t)e.second.version);
      put_str(b, e.second.value);
    }
    put_u32(b, (uint32_t)queues.size());
    for (auto& qe : queues) {
      put_str(b, qe.first);
      put_u64(b, (uint64_t)qe.second.next_id);
      put_u32(b, (uint32_t)(qe.second.ready.size() + qe.second.in_flight.size()));
      for (auto& m : qe.second.ready) { put_u64(b, (uint64_t)m.id); put_str(b, m.payload); }
      for (auto& f : qe.second.in_flight) {
        put_u64(b, (uint64_t)f.second.first.id);
        put_str(b, f.second.first.payload);
      }
    }
    put_u32(b, (uint32_t)objects.size());
    for (auto& be : objects) {
      put_str(b, be.first);
      put_u32(b, (uint32_t)be.second.size());
      for (auto& oe : be.second) { put_str(b, oe.first); put_str(b, oe.second); }
    }
    // every failure below leaves the previous snapshot intact and keeps
    // dirty set, so the 2s tick retries — renaming a short write over
    // the last good snapshot would LOSE durably-persisted state
    std::string tmp = persist_path + ".tmp";
    FILE* f = fopen(tmp.c_str(), "wb");
    if (!f) { perror("snapshot open"); return; }
    bool ok = fwrite(b.data(), 1, b.size(), f) == b.size();
    ok = (fflush(f) == 0) && ok;
    ok = (fsync(fileno(f)) == 0) && ok;
    fclose(f);
    if (!ok) { perror("snapshot write"); unlink(tmp.c_str()); return; }
    if (rename(tmp.c_str(), persist_path.c_str()) != 0) {
      perror("snapshot rename");
      return;
    }
    dirty = false;
    last_snap = now_s();
    // a durable snapshot folds in everything the WAL recorded: truncate
    // (a crash between rename and truncate is safe — replay skips
    // q_push records the snapshot already holds, and kv/obj records
    // are idempotent)
    wal_truncate();
  }

  void load_snapshot() {
    if (persist_path.empty()) return;
    std::unordered_map<std::string, int64_t> snap_next;
    FILE* f = fopen(persist_path.c_str(), "rb");
    if (f) {
      std::string b;
      char buf[1 << 16];
      size_t n;
      while ((n = fread(buf, 1, sizeof buf, f)) > 0) b.append(buf, n);
      fclose(f);
      if (b.size() < 9 || b.compare(0, 9, "DTPUSNAP1") != 0) {
        fprintf(stderr, "persist: unrecognized snapshot header, ignoring\n");
      } else {
        Rd r{b, 9};
        version = (int64_t)r.u64();
        for (uint32_t i = r.u32(); r.ok && i > 0; --i) {
          std::string key = r.str();
          Entry e;
          e.version = (int64_t)r.u64();
          e.value = r.str();
          if (r.ok) kv[key] = std::move(e);
        }
        for (uint32_t i = r.ok ? r.u32() : 0; r.ok && i > 0; --i) {
          std::string name = r.str();
          QueueState& q = queues[name];
          q.next_id = (int64_t)r.u64();
          snap_next[name] = q.next_id;
          for (uint32_t j = r.u32(); r.ok && j > 0; --j) {
            QMsg m;
            m.id = (int64_t)r.u64();
            m.payload = r.str();
            if (r.ok) q.ready.push_back(std::move(m));  // in-flight -> ready
          }
        }
        for (uint32_t i = r.ok ? r.u32() : 0; r.ok && i > 0; --i) {
          std::string bucket = r.str();
          for (uint32_t j = r.u32(); r.ok && j > 0; --j) {
            std::string nm = r.str();
            std::string data = r.str();
            if (r.ok) objects[bucket][nm] = std::move(data);
          }
        }
        if (!r.ok)
          fprintf(stderr, "persist: truncated snapshot (partial restore)\n");
      }
    }
    // then the op log: everything acked since that snapshot
    replay_wal(snap_next);
  }

  // ---- periodic sweep ---------------------------------------------------
  void sweep() {
    // durability tick: fold mutations into a snapshot at most every 2s
    if (dirty && !persist_path.empty() && now_s() - last_snap > 2.0)
      save_snapshot();
    double now = now_s();
    // A tick that comes late (the poll wakes every 100 ms) means this
    // process or its host was frozen: renewals sent meanwhile were read
    // just above, but a client frozen with us sent none. That deaf time
    // is not charged to the leases (store/memory.py does the same).
    double deaf = last_sweep > 0 ? now - last_sweep - 0.1 : 0;
    last_sweep = now;
    if (deaf > 0.5)
      for (auto& kv2 : leases) kv2.second.expires_at += deaf;
    std::vector<int64_t> expired;
    for (auto& kv2 : leases)
      if (kv2.second.expires_at <= now) expired.push_back(kv2.first);
    for (int64_t lid : expired) lease_revoke(lid);

    for (auto& qkv : queues) {
      auto& q = qkv.second;
      // redeliver timed-out in-flight messages (front of the queue)
      std::vector<int64_t> timed_out;
      for (auto& f : q.in_flight)
        if (f.second.second <= now) timed_out.push_back(f.first);
      for (int64_t mid : timed_out) {
        q.ready.push_front(std::move(q.in_flight[mid].first));
        q.in_flight.erase(mid);
      }
      // expire parked pops
      for (auto it = q.parked.begin(); it != q.parked.end();) {
        if (it->conn->dead) {
          it = q.parked.erase(it);
        } else if (it->deadline >= 0 && it->deadline <= now) {
          reply_ok(it->conn, it->rid, Val::nil());
          it = q.parked.erase(it);
        } else {
          ++it;
        }
      }
      if (!timed_out.empty()) serve_parked(qkv.first);
    }
  }

  // ---- connection lifecycle --------------------------------------------
  void drop_conn(Conn* c) {
    c->dead = true;
    for (int64_t sid : std::vector<int64_t>(c->stream_ids.begin(), c->stream_ids.end()))
      close_stream(c, sid, /*notify_end=*/false);
    for (int64_t lid : std::vector<int64_t>(c->leases.begin(), c->leases.end()))
      lease_revoke(lid);
    // Purge every raw Conn* reference BEFORE the Conn is destroyed: parked
    // queue pops (sweep()/serve_parked() would otherwise dereference freed
    // memory), plus any watch/sub registration whose sid drifted out of
    // c->stream_ids. conns.erase destroys the unique_ptr, so nothing may
    // point at c after this.
    for (auto& qkv : queues) {
      auto& parked = qkv.second.parked;
      parked.erase(std::remove_if(parked.begin(), parked.end(),
                                  [&](const ParkedPop& pp) { return pp.conn == c; }),
                   parked.end());
    }
    watches.erase(std::remove_if(watches.begin(), watches.end(),
                                 [&](const WatchReg& w) { return w.conn == c; }),
                  watches.end());
    subs.erase(std::remove_if(subs.begin(), subs.end(),
                              [&](const SubReg& s) { return s.conn == c; }),
               subs.end());
    close(c->fd);
    conns.erase(c->fd);
  }

  void pump_conn(Conn* c) {
    // parse complete frames from inbuf
    while (!c->dead) {
      if (c->inbuf.size() < 4) break;
      uint32_t len = (uint8_t)c->inbuf[0] | ((uint8_t)c->inbuf[1] << 8) |
                     ((uint8_t)c->inbuf[2] << 16) | ((uint8_t)c->inbuf[3] << 24);
      if (len > 256u * 1024 * 1024) { drop_conn(c); return; }
      if (c->inbuf.size() < 4 + (size_t)len) break;
      Decoder d{(const uint8_t*)c->inbuf.data() + 4, len};
      Val msg = d.decode();
      c->inbuf.erase(0, 4 + (size_t)len);
      if (!d.fail && msg.t == Val::MAP) handle(c, msg);
    }
  }

  // ---- main loop --------------------------------------------------------
  int run(const char* host, int port) {
    signal(SIGPIPE, SIG_IGN);
    load_snapshot();
    listen_fd = socket(AF_INET, SOCK_STREAM, 0);
    int one = 1;
    setsockopt(listen_fd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof one);
    sockaddr_in addr{};
    addr.sin_family = AF_INET;
    addr.sin_port = htons((uint16_t)port);
    if (inet_pton(AF_INET, host, &addr.sin_addr) != 1)
      addr.sin_addr.s_addr = INADDR_ANY;
    if (bind(listen_fd, (sockaddr*)&addr, sizeof addr) != 0) {
      perror("bind");
      return 1;
    }
    if (listen(listen_fd, 128) != 0) {
      perror("listen");
      return 1;
    }
    // report the actual port (port 0 = ephemeral) on stdout for drivers
    sockaddr_in bound{};
    socklen_t blen = sizeof bound;
    getsockname(listen_fd, (sockaddr*)&bound, &blen);
    printf("LISTENING %d\n", ntohs(bound.sin_port));
    fflush(stdout);

    std::vector<pollfd> fds;
    char buf[1 << 16];
    while (true) {
      fds.clear();
      fds.push_back({listen_fd, POLLIN, 0});
      for (auto& kv2 : conns) {
        short ev = POLLIN;
        if (!kv2.second->outbuf.empty()) ev |= POLLOUT;
        fds.push_back({kv2.first, ev, 0});
      }
      int rc = poll(fds.data(), (nfds_t)fds.size(), 100 /*ms: sweep tick*/);
      if (g_stop) {
        save_snapshot();
        return 0;
      }
      if (rc < 0 && errno != EINTR) {
        perror("poll");
        return 1;
      }
      if (fds[0].revents & POLLIN) {
        int fd = accept(listen_fd, nullptr, nullptr);
        if (fd >= 0) {
          fcntl(fd, F_SETFL, O_NONBLOCK);
          setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof one);
          auto c = std::make_unique<Conn>();
          c->fd = fd;
          conns[fd] = std::move(c);
        }
      }
      std::vector<Conn*> to_drop;
      for (size_t k = 1; k < fds.size(); ++k) {
        auto it = conns.find(fds[k].fd);
        if (it == conns.end()) continue;
        Conn* c = it->second.get();
        if (fds[k].revents & (POLLERR | POLLHUP)) {
          to_drop.push_back(c);
          continue;
        }
        if (fds[k].revents & POLLIN) {
          while (true) {
            ssize_t got = recv(c->fd, buf, sizeof buf, 0);
            if (got > 0) c->inbuf.append(buf, (size_t)got);
            else if (got == 0) { to_drop.push_back(c); break; }
            else if (errno == EAGAIN || errno == EWOULDBLOCK) break;
            else { to_drop.push_back(c); break; }
          }
          if (!c->dead) pump_conn(c);
        }
        if (fds[k].revents & POLLOUT) flush_conn(c, to_drop);
      }
      // writes generated by this tick's requests/streams
      for (auto& kv2 : conns)
        if (!kv2.second->outbuf.empty()) flush_conn(kv2.second.get(), to_drop);
      for (Conn* c : to_drop)
        if (conns.count(c->fd)) drop_conn(c);
      sweep();
    }
  }

  void flush_conn(Conn* c, std::vector<Conn*>& to_drop) {
    while (!c->outbuf.empty()) {
      ssize_t sent = send(c->fd, c->outbuf.data(), c->outbuf.size(), 0);
      if (sent > 0) c->outbuf.erase(0, (size_t)sent);
      else if (errno == EAGAIN || errno == EWOULDBLOCK) break;
      else {
        if (std::find(to_drop.begin(), to_drop.end(), c) == to_drop.end())
          to_drop.push_back(c);
        break;
      }
    }
  }
};

int main(int argc, char** argv) {
  const char* host = "0.0.0.0";
  int port = 4222;
  const char* persist = nullptr;
  bool fsync_wal = false;
  for (int i = 1; i < argc; ++i) {
    if (!strcmp(argv[i], "--fsync-wal")) { fsync_wal = true; continue; }
    if (i >= argc - 1) break;
    if (!strcmp(argv[i], "--host")) host = argv[++i];
    else if (!strcmp(argv[i], "--port")) port = atoi(argv[++i]);
    else if (!strcmp(argv[i], "--persist-path")) persist = argv[++i];
  }
  Server s;
  if (persist) {
    s.persist_path = persist;
    s.wal_path = std::string(persist) + ".wal";
    s.fsync_wal = fsync_wal;
  }
  // graceful shutdown: fold state into a final snapshot (the poll loop
  // notices g_stop via EINTR / its 100ms tick)
  struct sigaction sa{};
  sa.sa_handler = on_term;
  sigaction(SIGTERM, &sa, nullptr);
  sigaction(SIGINT, &sa, nullptr);
  return s.run(host, port);
}
